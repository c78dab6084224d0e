"""ctypes bindings for the native C++ roaring codec (native/roaring_codec.cpp).

The reference's storage hot loops are compiled Go; here they are C++
behind a C ABI.  The shared library is built on demand through the
shared loader (pilosa_tpu/nativelib.py), and every entry point degrades
to ``None`` so callers fall back to the vectorized-numpy codec when no
toolchain exists.  Set ``PILOSA_TPU_NO_NATIVE=1`` to force the Python
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
    "roaring_codec.cpp",
)
_LIB_STEM = "libpilosa_native"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
_has_fnv = False  # set at load(): the symbol is absent from older .so builds
_has_deser_into = False  # likewise (added with the ingest pipeline)


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


def _bind(lib: ctypes.CDLL) -> None:
        lib.rt_serialize.restype = ctypes.c_int
        lib.rt_serialize.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_size_t,
            ctypes.c_uint8,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.rt_serialize_words.restype = ctypes.c_int
        lib.rt_serialize_words.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64,
            ctypes.c_uint8,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.rt_deserialize.restype = ctypes.c_int
        lib.rt_deserialize.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.rt_popcount.restype = ctypes.c_uint64
        global _has_deser_into
        try:
            lib.rt_deserialize_into.restype = ctypes.c_int
            lib.rt_deserialize_into.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            _has_deser_into = True
        except AttributeError:
            _has_deser_into = False
        global _has_fnv
        try:
            lib.rt_fnv32a.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32,
            ]
            lib.rt_fnv32a.restype = ctypes.c_uint32
            _has_fnv = True
        except AttributeError:
            # an older prebuilt library without the symbol: fnv32a()
            # degrades to None like every other entry point
            _has_fnv = False
        lib.rt_popcount.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_size_t,
        ]
        lib.rt_free.restype = None
        lib.rt_free.argtypes = [ctypes.c_void_p]


def serialize(positions: np.ndarray, flags: int = 0) -> bytes | None:
    lib = load()
    if lib is None:
        return None
    positions = np.ascontiguousarray(positions, dtype=np.uint64)
    out = ctypes.POINTER(ctypes.c_uint8)()
    out_len = ctypes.c_size_t()
    rc = lib.rt_serialize(
        positions.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        positions.size,
        flags,
        ctypes.byref(out),
        ctypes.byref(out_len),
    )
    if rc != 0:
        return None
    try:
        return ctypes.string_at(out, out_len.value)
    finally:
        lib.rt_free(out)


def serialize_words(
    row_ids: np.ndarray,
    slots: np.ndarray,
    words: np.ndarray,
    flags: int = 0,
) -> bytes | None:
    """Roaring-serialize straight from dense row words (uint32
    [capacity, n_words] mirror; ``slots[r]`` is the word row of
    ascending ``row_ids[r]``) without materializing a positions array —
    byte-identical to ``serialize(positions)``.  None when
    unavailable."""
    lib = load()
    if lib is None:
        return None
    row_ids = np.ascontiguousarray(row_ids, dtype=np.uint64)
    slots = np.ascontiguousarray(slots, dtype=np.int64)
    if not words.flags["C_CONTIGUOUS"] or words.dtype != np.uint32:
        words = np.ascontiguousarray(words, dtype=np.uint32)
    out = ctypes.POINTER(ctypes.c_uint8)()
    out_len = ctypes.c_size_t()
    rc = lib.rt_serialize_words(
        row_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        slots.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        row_ids.size,
        words.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        words.shape[-1],
        flags,
        ctypes.byref(out),
        ctypes.byref(out_len),
    )
    if rc != 0:
        return None
    try:
        return ctypes.string_at(out, out_len.value)
    finally:
        lib.rt_free(out)


def deserialize(data: bytes) -> tuple[np.ndarray, int] | None:
    """(sorted positions, op count) or None on parse failure/unavailable."""
    lib = load()
    if lib is None:
        return None
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    out = ctypes.POINTER(ctypes.c_uint64)()
    out_n = ctypes.c_size_t()
    ops = ctypes.c_uint64()
    rc = lib.rt_deserialize(
        buf, len(data), ctypes.byref(out), ctypes.byref(out_n), ctypes.byref(ops)
    )
    if rc != 0:
        return None
    try:
        positions = np.ctypeslib.as_array(out, shape=(out_n.value,)).copy()
    finally:
        lib.rt_free(out)
    return positions.astype(np.uint64), int(ops.value)


def deserialize_into(
    data: bytes, out: np.ndarray
) -> tuple[int, int] | None:
    """Decode ``data`` directly into the caller's uint64 buffer ``out``
    (the staging-buffer zero-copy path: the input bytes are read in
    place and the positions land in ``out`` with no intermediate
    malloc/copy).  Returns (count, op_count); raises ValueError when
    ``out`` is too small, with the required capacity in the message;
    None on parse failure or when the library (or this symbol, in an
    older prebuilt .so) is unavailable."""
    lib = load()
    if lib is None or not _has_deser_into:
        return None
    src = np.frombuffer(data, dtype=np.uint8)  # zero-copy view
    if not (out.dtype == np.uint64 and out.flags["C_CONTIGUOUS"]):
        raise ValueError("staging buffer must be C-contiguous uint64")
    out_n = ctypes.c_size_t()
    ops = ctypes.c_uint64()
    rc = lib.rt_deserialize_into(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        src.size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        out.size,
        ctypes.byref(out_n),
        ctypes.byref(ops),
    )
    if rc == 3:
        raise ValueError(f"staging buffer too small: need {out_n.value}")
    if rc != 0:
        return None
    return int(out_n.value), int(ops.value)


def popcount(data: bytes | np.ndarray) -> int | None:
    lib = load()
    if lib is None:
        return None
    arr = np.ascontiguousarray(
        np.frombuffer(data, dtype=np.uint8) if isinstance(data, bytes) else data.view(np.uint8)
    )
    return int(
        lib.rt_popcount(
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), arr.size
        )
    )


def fnv32a(h: int, chunk: bytes) -> int | None:
    """One FNV-1a round over ``chunk`` continuing from ``h``; None when
    the native library (or this symbol, in an older prebuilt .so) is
    unavailable."""
    lib = load()
    if lib is None or not _has_fnv:
        return None
    return int(lib.rt_fnv32a(chunk, len(chunk), h))
