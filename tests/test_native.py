"""Native C++ codec vs numpy codec equivalence.

The C++ library (native/roaring_codec.cpp) must be byte-identical on
serialize and position-identical on deserialize for every container
encoding and op-log record type — the same matrix the reference covers in
roaring/roaring_internal_test.go.  Skips when no g++ toolchain exists.
"""

import numpy as np
import pytest

from pilosa_tpu.storage import _native, roaring

pytestmark = pytest.mark.skipif(
    _native.load() is None, reason="native toolchain unavailable"
)


CASES = {
    "empty": np.array([], dtype=np.uint64),
    "array": np.array([1, 5, 9, 70000, 2**40], dtype=np.uint64),
    "run": np.arange(10_000, 18_000, dtype=np.uint64),
    "bitmap": np.arange(0, 65536, 2, dtype=np.uint64),
    "mixed": np.concatenate(
        [
            np.arange(100, 5000, dtype=np.uint64),  # run
            np.arange(65536, 65536 + 30000, 3, dtype=np.uint64),  # bitmap
            np.array([2**33, 2**33 + 7], dtype=np.uint64),  # array
        ]
    ),
    "unsorted_dups": np.array([9, 1, 9, 5, 1, 2**21], dtype=np.uint64),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_serialize_bytes_identical(name):
    positions = CASES[name]
    assert _native.serialize(positions) == roaring._serialize_py(positions)


@pytest.mark.parametrize("name", sorted(CASES))
def test_roundtrip_native(name):
    positions = np.unique(CASES[name])
    data = _native.serialize(CASES[name])
    out, ops = _native.deserialize(data)
    assert ops == 0
    assert out.tolist() == positions.tolist()


def test_deserialize_matches_python_with_oplog():
    base = np.array([3, 10, 70000], dtype=np.uint64)
    data = roaring._serialize_py(base)
    data += roaring.encode_op(roaring.OP_ADD, 42)
    data += roaring.encode_op(roaring.OP_REMOVE, 10)
    data += roaring.encode_op(roaring.OP_ADD_BATCH, [100, 200, 2**30])
    data += roaring.encode_op(roaring.OP_REMOVE_BATCH, [3, 999])
    sub = roaring._serialize_py(np.array([7, 8, 9], dtype=np.uint64))
    data += roaring.encode_op(roaring.OP_ADD_ROARING, roaring=sub, op_n=3)
    sub2 = roaring._serialize_py(np.array([8, 200], dtype=np.uint64))
    data += roaring.encode_op(roaring.OP_REMOVE_ROARING, roaring=sub2, op_n=2)

    got, got_ops = _native.deserialize(data)
    want, want_ops = roaring._deserialize_py(data)
    assert got.tolist() == want.tolist()
    assert got_ops == want_ops
    assert got.tolist() == [7, 9, 42, 100, 70000, 2**30]


def test_corrupt_oplog_truncates_same_as_python():
    base = np.array([1, 2, 3], dtype=np.uint64)
    data = roaring._serialize_py(base)
    data += roaring.encode_op(roaring.OP_ADD, 50)
    good_len = len(data)
    data += b"\x00garbage-that-fails-checksum"
    got, _ = _native.deserialize(data)
    want, _ = roaring._deserialize_py(data)
    assert got.tolist() == want.tolist() == [1, 2, 3, 50]
    # sanity: the garbage really was past a valid record boundary
    assert len(data) > good_len


def test_hostile_oplog_length_no_overflow():
    """A kOpAddRoaring record claiming a ~2^64-byte payload must not wrap
    the bounds check and read off the buffer (segfault on hostile fragment
    bytes via /internal/fragment/data)."""
    import struct

    base = roaring._serialize_py(np.array([1, 2, 3], dtype=np.uint64))
    for op_byte in (roaring.OP_ADD_ROARING, roaring.OP_REMOVE_ROARING):
        for length in (2**64 - 1, 2**64 - 4, 2**64 - 17, 2**63):
            data = bytes(base) + struct.pack(
                "<BQI", op_byte, length, 0xDEADBEEF
            ) + b"\x00\x00\x00\x00"
            got, _ = _native.deserialize(data)
            assert got.tolist() == [1, 2, 3]
    # batch ops: value*8 wrapping must be rejected too
    for op_byte in (roaring.OP_ADD_BATCH, roaring.OP_REMOVE_BATCH):
        for length in (2**61, 2**64 - 1):
            data = bytes(base) + struct.pack("<BQI", op_byte, length, 0)
            got, _ = _native.deserialize(data)
            assert got.tolist() == [1, 2, 3]


def test_official_format_parse():
    # Build an official-spec file via the existing python test helper path:
    # reuse roaring's serializer for positions in pilosa format, then
    # hand-craft a small official no-run file.
    import struct

    vals = [1, 3, 4, 5, 100]
    out = struct.pack("<II", roaring.COOKIE_NO_RUN, 1)
    out += struct.pack("<HH", 0, len(vals) - 1)
    out += struct.pack("<I", len(out) + 4)
    out += np.array(vals, dtype="<u2").tobytes()
    got, ops = _native.deserialize(out)
    want, _ = roaring._deserialize_py(out)
    assert got.tolist() == want.tolist() == vals
    assert ops == 0


def test_native_popcount():
    arr = np.array([0xFFFFFFFF, 0, 0b1011], dtype=np.uint32)
    assert _native.popcount(arr) == 32 + 0 + 3
    assert _native.popcount(arr.tobytes()) == 35


def test_fuzz_roundtrip_random():
    rng = np.random.default_rng(99)
    for _ in range(25):
        n = int(rng.integers(0, 5000))
        positions = rng.integers(0, 2**48, size=n, dtype=np.uint64)
        nat = _native.serialize(positions)
        py = roaring._serialize_py(positions)
        assert nat == py
        got, _ = _native.deserialize(nat)
        assert got.tolist() == np.unique(positions).tolist()


def test_fuzz_corrupt_inputs_dont_crash():
    """Reference fuzzes bitmap unmarshal (roaring/fuzzer.go); the native
    reader must reject or truncate garbage without crashing the process."""
    rng = np.random.default_rng(7)
    base = roaring._serialize_py(np.arange(0, 3000, 2, dtype=np.uint64))
    for _ in range(50):
        buf = bytearray(base)
        for _ in range(int(rng.integers(1, 8))):
            buf[int(rng.integers(0, len(buf)))] = int(rng.integers(0, 256))
        try:
            res = _native.deserialize(bytes(buf))
        except Exception as e:  # must never segfault; python-level errors ok
            pytest.fail(f"native deserialize raised {e!r}")
        if res is not None:
            positions, _ = res
            assert positions.dtype == np.uint64


class TestSerializeWords:
    """rt_serialize_words (the snapshot hot path) must be byte-identical
    to the positions pipeline for every container type and row width."""

    def _positions_of(self, rows, n_words):
        from pilosa_tpu.ops import bitops

        parts = [
            bitops.unpack_columns(w)
            + np.uint64(r) * np.uint64(n_words * 32)
            for r, w in rows
        ]
        return np.sort(np.concatenate(parts)) if parts else np.empty(
            0, np.uint64
        )

    def _check(self, rows, n_words):
        row_ids = np.array([r for r, _ in rows], dtype=np.uint64)
        words = (
            np.stack([w for _, w in rows])
            if rows
            else np.empty((0, n_words), np.uint32)
        )
        got = roaring.serialize_rows(row_ids, words)
        want = roaring.serialize(self._positions_of(rows, n_words))
        assert got == want

    def test_aligned_width_all_container_types(self):
        # n_words % 2048 == 0: the container-aligned fast path
        rng = np.random.default_rng(7)
        nw = 4096  # 2 containers per row
        sparse = np.zeros(nw, np.uint32)
        idx = rng.choice(nw * 32, 300, replace=False)
        np.bitwise_or.at(
            sparse, idx // 32, np.uint32(1) << (idx % 32).astype(np.uint32)
        )
        dense = rng.integers(0, 2**32, size=nw, dtype=np.uint32)
        runs = np.zeros(nw, np.uint32)
        runs[100:600] = 0xFFFFFFFF
        empty = np.zeros(nw, np.uint32)
        self._check(
            [(0, sparse), (3, dense), (9, runs), (11, empty),
             (2**40, dense)],
            nw,
        )

    def test_narrow_width_rows_share_containers(self):
        # n_words % 2048 != 0: rows pack into shared containers via the
        # streaming path
        rng = np.random.default_rng(9)
        nw = 512  # 2^14 bits/row: 4 rows per 65536-bit container
        rows = [
            (r, rng.integers(0, 2**32, size=nw, dtype=np.uint32)
             & rng.integers(0, 2**32, size=nw, dtype=np.uint32))
            for r in range(6)
        ]
        self._check(rows, nw)

    def test_empty(self):
        self._check([], 2048)


class TestImportMergeParity:
    """ph_import_merge (native one-pass import) vs the numpy fallback:
    identical changed counts and mirror state for set and clear, on both
    the id-keyed fast path and the compact-key (huge hashed row ids)
    path."""

    def _pair(self, rows, cols, monkeypatch):
        import pilosa_tpu.ops._hostops as ho
        from pilosa_tpu.core.fragment import Fragment

        # the class-level skip gates on the CODEC library; this class
        # exercises the separate hostops library — a hostops build
        # failure must fail loudly, not silently compare numpy to numpy
        assert ho.load() is not None, "hostops library unavailable"
        f_native = Fragment(n_words=256)
        n_native = f_native.import_bits(rows.copy(), cols.copy())
        # force the numpy fallback for the twin
        monkeypatch.setattr(ho, "load", lambda: None)
        f_numpy = Fragment(n_words=256)
        n_numpy = f_numpy.import_bits(rows.copy(), cols.copy())
        monkeypatch.undo()
        return f_native, n_native, f_numpy, n_numpy

    def _assert_same(self, f_a, f_b, rows):
        for r in np.unique(rows):
            np.testing.assert_array_equal(
                f_a.row_words_host(int(r)), f_b.row_words_host(int(r))
            )

    def test_set_and_clear_small_ids(self, monkeypatch):
        rng = np.random.default_rng(5)
        rows = rng.integers(0, 40, size=5000).astype(np.uint64)
        cols = rng.integers(0, 256 * 32, size=5000).astype(np.uint64)
        fa, na, fb, nb = self._pair(rows, cols, monkeypatch)
        assert na == nb
        self._assert_same(fa, fb, rows)
        import pilosa_tpu.ops._hostops as ho

        crows, ccols = rows[:2000], cols[:2000]
        ca = fa.import_bits(crows.copy(), ccols.copy(), clear=True)
        monkeypatch.setattr(ho, "load", lambda: None)
        cb = fb.import_bits(crows.copy(), ccols.copy(), clear=True)
        monkeypatch.undo()
        assert ca == cb
        self._assert_same(fa, fb, rows)

    def test_huge_hashed_row_ids_compact_path(self, monkeypatch):
        # row ids too large for id*width to fit int63: the compact-key
        # path (searchsorted inverse) must engage and agree
        rng = np.random.default_rng(6)
        base = np.uint64(2**55)
        rows = (base + rng.integers(0, 5, size=3000).astype(np.uint64))
        cols = rng.integers(0, 256 * 32, size=3000).astype(np.uint64)
        fa, na, fb, nb = self._pair(rows, cols, monkeypatch)
        assert na == nb and na > 0
        self._assert_same(fa, fb, rows)

    def test_maintained_counts_carry(self):
        # per-row changed counts from the native pass must keep the
        # maintained TopN counts exact across a second import
        from pilosa_tpu.core.fragment import Fragment

        rng = np.random.default_rng(8)
        f = Fragment(n_words=256)
        rows = rng.integers(0, 8, size=2000).astype(np.uint64)
        cols = rng.integers(0, 256 * 32, size=2000).astype(np.uint64)
        f.import_bits(rows, cols)
        _ = f.row_counts()  # build counts
        f.import_bits(
            rng.integers(0, 8, size=500).astype(np.uint64),
            rng.integers(0, 256 * 32, size=500).astype(np.uint64),
        )
        assert f._counts is not None  # carried, not invalidated
        ids, counts = f.row_counts()
        for r, c in zip(ids, counts.tolist()):
            want = int(np.bitwise_count(f.row_words_host(int(r))).sum())
            assert c == want, r


def test_fuzz_import_merge_differential(monkeypatch):
    """Differential fuzz: random (shape, id regime, set/clear
    interleaving) sequences must leave the native and numpy import
    paths with identical mirrors and changed counts."""
    import pilosa_tpu.ops._hostops as ho
    from pilosa_tpu.core.fragment import Fragment

    assert ho.load() is not None, "hostops library unavailable"
    root_rng = np.random.default_rng(0xF00D)
    for case in range(12):
        n_words = int(root_rng.choice([32, 64, 256, 2048]))
        width = n_words * 32
        if case % 3 == 2:
            row_base = np.uint64(2**55)  # compact-key path
        else:
            row_base = np.uint64(0)  # id-keyed fast path
        n_rows = int(root_rng.integers(1, 60))
        f_nat = Fragment(n_words=n_words)
        f_np = Fragment(n_words=n_words)
        for step in range(int(root_rng.integers(1, 5))):
            n = int(root_rng.integers(1, 4000))
            rows = row_base + root_rng.integers(
                0, n_rows, size=n
            ).astype(np.uint64)
            cols = root_rng.integers(0, width, size=n).astype(np.uint64)
            clear = bool(root_rng.integers(0, 2)) and step > 0
            a = f_nat.import_bits(rows.copy(), cols.copy(), clear=clear)
            monkeypatch.setattr(ho, "load", lambda: None)
            b = f_np.import_bits(rows.copy(), cols.copy(), clear=clear)
            monkeypatch.undo()
            assert a == b, (case, step, a, b)
            for r in np.unique(rows):
                np.testing.assert_array_equal(
                    f_nat.row_words_host(int(r)),
                    f_np.row_words_host(int(r)),
                    err_msg=f"case {case} step {step} row {r}",
                )


def test_import_merge_absent_row_id_skipped():
    """id_keys=1: a row id missing from the fragment's sorted row table
    (caller invariant break) must be skipped — the unguarded binary
    search used to land on the successor row and corrupt it, or read
    slots[]/row_ids[] out of bounds past the last row."""
    import pilosa_tpu.ops._hostops as ho

    assert ho.load() is not None, "hostops library unavailable"
    n_words = 8
    width = n_words * 32
    row_ids = np.array([2, 7, 9], np.uint64)
    slots = np.arange(3, dtype=np.int64)
    mirror = np.zeros((4, n_words), np.uint32)
    # rid 5 falls between table entries; rid 11 is past the end
    raw = [(2, 1), (2, 40), (5, 3), (5, 99), (9, 7), (11, 0)]
    keys = np.sort(np.array([r * width + c for r, c in raw], np.int64))
    nc, wal, perrow, cw = ho.import_merge(
        keys, width, n_words, slots, row_ids, mirror, False, id_keys=True
    )
    assert nc == 3
    assert wal.tolist() == [2 * width + 1, 2 * width + 40, 9 * width + 7]
    assert perrow.tolist() == [2, 0, 1]
    assert cw.tolist() == [0, 1, 2 * n_words + 0]
    want = np.zeros((4, n_words), np.uint32)
    want[0, 0] = 1 << 1
    want[0, 1] = 1 << 8
    want[2, 0] = 1 << 7
    np.testing.assert_array_equal(mirror, want)

    # fuzz the skip semantics against a python reference
    rng = np.random.default_rng(0xABE)
    for case in range(8):
        nw = int(rng.choice([4, 8, 32]))
        w = nw * 32
        table = np.unique(rng.integers(0, 30, size=rng.integers(1, 10)))
        table = table.astype(np.uint64)
        slots_f = np.arange(table.size, dtype=np.int64)
        mir = np.zeros((table.size + 1, nw), np.uint32)
        rids = rng.integers(0, 32, size=200).astype(np.int64)  # some absent
        cols = rng.integers(0, w, size=200).astype(np.int64)
        ks = np.sort(rids * w + cols)
        clear = bool(case % 2)
        if clear:
            mir[:-1] = 0xFFFFFFFF  # all bits set so clears change things
        ref = mir.copy()
        n_ref = 0
        pos = {int(r): i for i, r in enumerate(table)}
        for k in ks.tolist():
            r, c = divmod(int(k), w)
            if r not in pos:
                continue
            word, bit = c >> 5, np.uint32(1 << (c & 31))
            if clear:
                if ref[pos[r], word] & bit:
                    ref[pos[r], word] &= ~bit
                    n_ref += 1
            else:
                if not ref[pos[r], word] & bit:
                    ref[pos[r], word] |= bit
                    n_ref += 1
        got = ho.import_merge(
            ks, w, nw, slots_f, table, mir, clear, id_keys=True
        )
        assert got[0] == n_ref, (case, got[0], n_ref)
        np.testing.assert_array_equal(mir, ref, err_msg=f"case {case}")


# -- nativelib: a library is named by what it was built from ------------------

_TINY_SRC = 'extern "C" int tiny_answer() { return 42; }\n'


def _bind_tiny(lib):
    import ctypes

    lib.tiny_answer.restype = ctypes.c_int
    lib.tiny_answer.argtypes = []


def test_nativelib_name_follows_source_flags_and_cpu(tmp_path, monkeypatch):
    """A library built elsewhere (other source, other flags, other CPU)
    lives under another name, so it is never found, only rebuilt."""
    from pilosa_tpu import nativelib

    src = tmp_path / "tiny.cpp"
    src.write_text(_TINY_SRC)
    here = nativelib.lib_path(str(src), "libtiny")
    assert here == nativelib.lib_path(str(src), "libtiny")
    assert here != nativelib.lib_path(str(src), "libtiny", extra=())
    monkeypatch.setattr(nativelib, "_cpu_flags", lambda: "another cpu")
    assert nativelib.lib_path(str(src), "libtiny") != here
    monkeypatch.undo()
    src.write_text(_TINY_SRC + "// edited\n")
    assert nativelib.lib_path(str(src), "libtiny") != here


def test_nativelib_ignores_a_library_it_did_not_build(tmp_path):
    from pilosa_tpu import nativelib

    src = tmp_path / "tiny.cpp"
    src.write_text(_TINY_SRC)
    # what the old loader would have picked up: a fixed name, newer than
    # the source, from who knows which machine
    (tmp_path / "libtiny.so").write_bytes(b"not a library")
    lib = nativelib.load(str(src), "libtiny", _bind_tiny)
    assert lib is not None and lib.tiny_answer() == 42
    st = nativelib.status()["libtiny"]
    assert st["built"] and st["path"] == nativelib.lib_path(str(src), "libtiny")
    # a second load finds this machine's own build and does not rebuild
    assert nativelib.load(str(src), "libtiny", _bind_tiny) is not None
    assert nativelib.status()["libtiny"]["built"] is False


def test_nativelib_build_failure_is_logged_with_the_compilers_words(
    tmp_path, caplog
):
    from pilosa_tpu import nativelib

    src = tmp_path / "broken.cpp"
    src.write_text("this is not C++;\n")
    with caplog.at_level("WARNING", logger="pilosa_tpu.nativelib"):
        assert nativelib.load(str(src), "libbroken", _bind_tiny) is None
    st = nativelib.status()["libbroken"]
    assert st["path"] is None and "error" in st["error"]
    assert any("broken.cpp" in r.getMessage() for r in caplog.records)
    assert list(tmp_path.glob("*.so*")) == []  # no temp file left behind
