"""Shared build-on-demand loader for the native C++ libraries.

All native components (the roaring codec, storage/_native.py; the host
latency-tier kernels, ops/_hostops.py; the reference anchors,
ops/_refanchor.py) follow the same contract: the .so is compiled next to
its source with g++ on first use, every entry point degrades to a Python
fallback when no toolchain exists, and ``PILOSA_TPU_NO_NATIVE=1`` forces
the fallback.

A built library is named by a hash of what went into it — the source
bytes, the compiler flags and the CPU's feature-flag line — so a binary
is only ever found by a machine that would have produced the same one.
A tree copied from elsewhere (``-march=native`` code for another CPU, a
stale build of older source) simply has no file under the expected name
and a fresh one is built; file times play no part.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from typing import Callable

logger = logging.getLogger(__name__)

_BASE_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC")
# -march=native first (popcnt/AVX on x86); plain -O3 for toolchains that
# reject it
_FLAG_VARIANTS = (("-march=native",), ())

# stem -> {"path": loaded .so or None, "built": compiled by this process,
# "error": why it is unavailable}; read by /debug/vars so an operator (and
# chip_smoke.py) can see which tier is native without guessing
_status: dict[str, dict] = {}
_status_lock = threading.Lock()


def status() -> dict[str, dict]:
    """Outcome of every :func:`load` this process attempted."""
    with _status_lock:
        return {k: dict(v) for k, v in _status.items()}


def _cpu_flags() -> str:
    """The CPU feature line ``-march=native`` specialises for."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    import platform

    return platform.machine()


def lib_path(src: str, stem: str, extra: tuple[str, ...] = _FLAG_VARIANTS[0]) -> str:
    """Where the library built from ``src`` with ``extra`` flags on this
    CPU lives: ``<dir of src>/<stem>.<hash>.so``."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_BASE_FLAGS + tuple(extra)).encode())
    h.update(_cpu_flags().encode())
    return os.path.join(
        os.path.dirname(src), f"{stem}.{h.hexdigest()[:16]}.so"
    )


def build(src: str, out_path: str, extra: tuple[str, ...] = ()) -> str | None:
    """Compile ``src`` into ``out_path`` atomically; None on success,
    else the reason (g++'s stderr).

    The object is written to a PER-PROCESS temp name and os.replace'd
    in: two processes building concurrently (cluster nodes on one host,
    parallel test workers) each produce a complete .so and the last
    rename wins — a shared fixed temp name would interleave their
    compiler output into a permanently corrupt library."""
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(out_path) or ".", suffix=".so.tmp"
    )
    os.close(fd)
    try:
        cmd = ["g++", *_BASE_FLAGS, *extra, src, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        except subprocess.CalledProcessError as e:
            return e.stderr.decode("utf-8", "replace").strip() or str(e)
        except (OSError, subprocess.SubprocessError) as e:
            return f"{type(e).__name__}: {e}"
        os.replace(tmp, out_path)
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass


def load(src: str, stem: str, bind: Callable[[ctypes.CDLL], None]):
    """Load the library for ``src``, building it when this machine has
    not yet; None when unavailable for any reason — toolchain absent,
    build or load failure (logged with the compiler's message), or
    ``bind`` raising AttributeError.  Callers cache the result under
    their own lock."""
    rec = {"path": None, "built": False, "error": None}
    lib = None
    if os.environ.get("PILOSA_TPU_NO_NATIVE"):
        rec["error"] = "disabled by PILOSA_TPU_NO_NATIVE"
    elif not os.path.exists(src):
        rec["error"] = f"source missing: {src}"
    else:
        lib = _load_or_build(src, stem, bind, rec)
    with _status_lock:
        _status[stem] = rec
    return lib


def _load_or_build(src, stem, bind, rec):
    for extra in _FLAG_VARIANTS:
        path = lib_path(src, stem, extra)
        if not os.path.exists(path):
            err = build(src, path, extra)
            if err is not None:
                rec["error"] = err
                logger.warning(
                    "native build of %s (%s) failed: %s",
                    os.path.basename(src), " ".join(extra) or "plain", err,
                )
                continue
            rec["built"] = True
        try:
            lib = ctypes.CDLL(path)
            bind(lib)
        except (OSError, AttributeError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
            logger.warning("native library %s unusable: %s", path, e)
            continue
        rec["path"] = path
        rec["error"] = None
        return lib
    return None
