"""ctypes bindings for the reference-anchor library
(native/refanchor.cpp): a compiled port of the semantic work of the
reference's hot benchmark paths (roaring containers, AddN, CountRange,
intersectionCount, snapshot serialization), used as the measured
comparison baseline in tools/ref_anchor.py (BASELINE.md).

Built on demand through the shared loader (pilosa_tpu/nativelib.py);
``load()`` returns None when no toolchain exists — callers must skip
the anchor then (there is no Python fallback: an interpreted anchor
would flatter the repo's numbers, which defeats its purpose).
"""

from __future__ import annotations

import ctypes
import os
import threading

import numpy as np

from pilosa_tpu import nativelib

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "refanchor.cpp",
)
_LIB_STEM = "libpilosa_refanchor"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_U64P = ctypes.POINTER(ctypes.c_uint64)


def _bind(lib: ctypes.CDLL) -> None:
    lib.ra_new.restype = ctypes.c_void_p
    lib.ra_new.argtypes = []
    lib.ra_free.restype = None
    lib.ra_free.argtypes = [ctypes.c_void_p]
    lib.ra_addn_sorted.restype = ctypes.c_uint64
    lib.ra_addn_sorted.argtypes = [ctypes.c_void_p, _U64P, ctypes.c_size_t]
    lib.ra_count_range.restype = ctypes.c_uint64
    lib.ra_count_range.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
    ]
    lib.ra_intersection_count.restype = ctypes.c_uint64
    lib.ra_intersection_count.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
    ]
    lib.ra_intersection_count_many.restype = ctypes.c_uint64
    lib.ra_intersection_count_many.argtypes = [
        ctypes.c_void_p, _U64P, _U64P, ctypes.c_size_t, ctypes.c_uint64,
    ]
    lib.ra_snapshot.restype = ctypes.c_int64
    lib.ra_snapshot.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ra_count.restype = ctypes.c_uint64
    lib.ra_count.argtypes = [ctypes.c_void_p]


def load() -> ctypes.CDLL | None:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _lib = nativelib.load(_SRC, _LIB_STEM, _bind)
        return _lib


class RefBitmap:
    """A reference-semantics roaring bitmap handle."""

    def __init__(self):
        lib = load()
        if lib is None:
            raise RuntimeError("refanchor library unavailable")
        self._lib = lib
        self._h = lib.ra_new()

    def close(self) -> None:
        if self._h:
            self._lib.ra_free(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def addn_sorted(self, positions: np.ndarray) -> int:
        """Bulk-add sorted, deduped uint64 positions; changed count."""
        positions = np.ascontiguousarray(positions, dtype=np.uint64)
        return int(
            self._lib.ra_addn_sorted(
                self._h, positions.ctypes.data_as(_U64P), positions.size
            )
        )

    def count_range(self, lo: int, hi: int) -> int:
        return int(self._lib.ra_count_range(self._h, lo, hi))

    def intersection_count(self, row_a: int, row_b: int, shard_width: int) -> int:
        return int(
            self._lib.ra_intersection_count(self._h, row_a, row_b, shard_width)
        )

    def intersection_count_many(
        self, rows_a: np.ndarray, rows_b: np.ndarray, shard_width: int
    ) -> int:
        """Sum of per-pair intersection counts in ONE native crossing
        (the reference fans shards in-process; per-pair ctypes calls
        would bias the anchor slow)."""
        rows_a = np.ascontiguousarray(rows_a, dtype=np.uint64)
        rows_b = np.ascontiguousarray(rows_b, dtype=np.uint64)
        return int(
            self._lib.ra_intersection_count_many(
                self._h,
                rows_a.ctypes.data_as(_U64P),
                rows_b.ctypes.data_as(_U64P),
                rows_a.size,
                shard_width,
            )
        )

    def snapshot(self, path: str) -> int:
        n = int(self._lib.ra_snapshot(self._h, path.encode()))
        if n < 0:
            raise OSError(f"refanchor snapshot failed: {path}")
        return n

    def count(self) -> int:
        return int(self._lib.ra_count(self._h))
