"""ctypes bindings for the host latency-tier kernels (native/hostops.cpp).

Built on demand through the shared loader (pilosa_tpu/nativelib.py);
every entry point degrades to numpy (``np.bitwise_count``) when no
toolchain exists.  Set ``PILOSA_TPU_NO_NATIVE=1`` to force the numpy
path.
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
    "hostops.cpp",
)
_LIB_STEM = "libpilosa_hostops"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_U8P = ctypes.POINTER(ctypes.c_uint8)
_U64P = ctypes.POINTER(ctypes.c_uint64)

# PQL set-op name -> native op code (native/hostops.cpp enum Op)
OP_CODES = {"intersect": 0, "union": 1, "difference": 2, "xor": 3}


_I64P = ctypes.POINTER(ctypes.c_int64)


def _bind(lib: ctypes.CDLL) -> None:
    lib.ph_popcount.restype = ctypes.c_uint64
    lib.ph_popcount.argtypes = [_U8P, ctypes.c_size_t]
    lib.ph_import_merge.restype = ctypes.c_int64
    lib.ph_import_merge.argtypes = [
        _I64P, ctypes.c_size_t, ctypes.c_int64, ctypes.c_int64,
        _I64P, _U64P, ctypes.c_size_t, ctypes.c_int, _U8P, ctypes.c_int,
        _U64P, _I64P, _I64P, _I64P,
    ]
    lib.ph_pair_count.restype = ctypes.c_uint64
    lib.ph_pair_count.argtypes = [
        _U8P, _U8P, ctypes.c_size_t, ctypes.c_int,
    ]
    lib.ph_pair_op.restype = None
    lib.ph_pair_op.argtypes = [
        _U8P, _U8P, _U8P, ctypes.c_size_t, ctypes.c_int,
    ]
    lib.ph_extract.restype = ctypes.c_size_t
    lib.ph_extract.argtypes = [_U8P, ctypes.c_size_t, ctypes.c_uint64, _U64P]
    lib.ph_pair_count_addr.restype = ctypes.c_uint64
    lib.ph_pair_count_addr.argtypes = [
        _U64P, _U64P, ctypes.c_size_t, ctypes.c_size_t, ctypes.c_int,
    ]


def load() -> ctypes.CDLL | None:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _lib = nativelib.load(_SRC, _LIB_STEM, _bind)
        return _lib


def _u8(a: np.ndarray):
    return a.ctypes.data_as(_U8P)


def popcount(words: np.ndarray) -> int:
    """Total set bits of a C-contiguous uint32 array (any shape)."""
    lib = load()
    words = np.ascontiguousarray(words, dtype=np.uint32)
    if lib is None:
        return int(np.bitwise_count(words).sum(dtype=np.uint64))
    return int(lib.ph_popcount(_u8(words), words.size))


def pair_count(a: np.ndarray, b: np.ndarray, op: str) -> int:
    """Fused ``popcount(op(a, b))`` without materializing the op —
    the host twin of ops/bitops.py's jitted *_count kernels (reference
    roaring.go:568)."""
    lib = load()
    a = np.ascontiguousarray(a, dtype=np.uint32)
    b = np.ascontiguousarray(b, dtype=np.uint32)
    if a.size != b.size:
        raise ValueError("pair_count operands differ in size")
    if lib is None:
        if op == "intersect":
            x = a & b
        elif op == "union":
            x = a | b
        elif op == "difference":
            x = a & ~b
        else:
            x = a ^ b
        return int(np.bitwise_count(x).sum(dtype=np.uint64))
    return int(lib.ph_pair_count(_u8(a), _u8(b), a.size, OP_CODES[op]))


def pair_count_addrs(
    addr_a: np.ndarray, addr_b: np.ndarray, n_words: int, op: str
) -> int | None:
    """Sum of fused pair counts over rows given by ABSOLUTE addresses
    (uint64 numpy arrays) — the zero-marshalling latency-tier entry:
    the caller computes ``base + slot*stride`` vectorized and this
    makes one ctypes crossing for the whole shard fan.  The caller owns
    keeping the backing arrays alive and locked for the duration.
    None when no native library is available."""
    lib = load()
    if lib is None:
        return None
    addr_a = np.ascontiguousarray(addr_a, dtype=np.uint64)
    addr_b = np.ascontiguousarray(addr_b, dtype=np.uint64)
    return int(
        lib.ph_pair_count_addr(
            addr_a.ctypes.data_as(_U64P),
            addr_b.ctypes.data_as(_U64P),
            addr_a.size, n_words, OP_CODES[op],
        )
    )


def extract_positions(words: np.ndarray, base: int = 0) -> np.ndarray | None:
    """Set-bit offsets (+ ``base``) of a contiguous uint32 word vector,
    ascending — the ctz walk behind snapshot encoding; None when no
    native library is available (callers keep their numpy path)."""
    lib = load()
    if lib is None:
        return None
    words = np.ascontiguousarray(words, dtype=np.uint32)
    n = int(lib.ph_popcount(_u8(words), words.size))
    out = np.empty(n, dtype=np.uint64)
    k = lib.ph_extract(
        _u8(words), words.size, ctypes.c_uint64(base),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return out[:k]


def import_merge(
    keys: np.ndarray,
    width: int,
    n_words: int,
    slots: np.ndarray,
    row_ids: np.ndarray,
    mirror: np.ndarray,
    clear: bool,
    id_keys: bool = False,
    want_wal: bool = True,
) -> tuple[int, np.ndarray | None, np.ndarray, np.ndarray] | None:
    """One native pass over SORTED keys (``row_index*width + col``, or
    ``row_id*width + col`` with ``id_keys=True``; duplicates allowed):
    apply the bulk set/clear to ``mirror`` (uint32 [capacity, n_words],
    mutated in place) and return
    ``(n_changed, wal_positions, perrow_changed, changed_word_indices)``
    — everything Fragment.import_bits needs after the merge.  None when
    no native library is available (callers keep their numpy path).
    ``want_wal=False`` skips the WAL-position extraction (and its
    keys.size allocation) — store-less fragments have no op log to
    feed, and the ingest pipeline's merged applies make that array the
    largest allocation of the whole pass.  The caller owns key bounds
    and holds the fragment lock."""
    lib = load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    slots = np.ascontiguousarray(slots, dtype=np.int64)
    row_ids = np.ascontiguousarray(row_ids, dtype=np.uint64)
    wal = np.empty(keys.size, dtype=np.uint64) if want_wal else None
    perrow = np.zeros(slots.size, dtype=np.int64)
    cw = np.empty(keys.size, dtype=np.int64)
    ncw = np.zeros(1, dtype=np.int64)
    nc = int(
        lib.ph_import_merge(
            keys.ctypes.data_as(_I64P), keys.size, width, n_words,
            slots.ctypes.data_as(_I64P),
            row_ids.ctypes.data_as(_U64P), row_ids.size, int(id_keys),
            _u8(mirror), int(clear),
            wal.ctypes.data_as(_U64P) if wal is not None else None,
            perrow.ctypes.data_as(_I64P),
            cw.ctypes.data_as(_I64P),
            ncw.ctypes.data_as(_I64P),
        )
    )
    return nc, wal[:nc] if wal is not None else None, perrow, cw[: int(ncw[0])]


def pair_op(a: np.ndarray, b: np.ndarray, op: str) -> np.ndarray:
    """Materialized ``op(a, b)`` into a fresh array (numpy-compatible
    semantics, native single pass)."""
    lib = load()
    a = np.ascontiguousarray(a, dtype=np.uint32)
    b = np.ascontiguousarray(b, dtype=np.uint32)
    if lib is None:
        if op == "intersect":
            return a & b
        if op == "union":
            return a | b
        if op == "difference":
            return a & ~b
        return a ^ b
    out = np.empty_like(a)
    lib.ph_pair_op(_u8(a), _u8(b), _u8(out), a.size, OP_CODES[op])
    return out
