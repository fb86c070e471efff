"""Native (C++) data-path kernels, compiled once at first use.

The image ships no pybind11 and nothing may be pip-installed, so the
binding is ctypes over a g++-built shared object (the toolchain IS baked
in). The build is lazy, from the committed ``datapack.cc`` alone, and
cached under ``AREAL_NATIVE_CACHE`` (default ``~/.cache/areal_tpu/native``)
keyed by the source hash, so a fresh machine builds it once, inside its
run. Any failure — no compiler, read-only cache, load error — falls back
to the pure-Python implementations, which remain the semantic reference;
the fallback is logged, and ``implementation()`` says which one is active
(chip_smoke.py prints it in its ``device`` line).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

from areal_tpu.utils import logging as alog

logger = alog.getLogger("native")

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_failed = False


def _cache_dir() -> str:
    return os.environ.get(
        "AREAL_NATIVE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "areal_tpu", "native"),
    )


def _build(src: str, tag: str) -> str:
    """Compile ``src`` into the cache keyed by source hash; reuse if fresh."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out_dir = _cache_dir()
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, f"_{tag}_{digest}.so")
    if os.path.exists(out):
        return out
    tmp = out + f".tmp.{os.getpid()}"
    subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp, src],
        check=True,
        capture_output=True,
        timeout=120,
    )
    os.replace(tmp, out)  # atomic: concurrent builders race benignly
    return out


def datapack_lib() -> ctypes.CDLL | None:
    """The compiled datapack kernels, or None (callers fall back)."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            path = _build(os.path.join(_SRC_DIR, "datapack.cc"), "datapack")
            lib = ctypes.CDLL(path)
            i64p = ctypes.POINTER(ctypes.c_int64)
            i32p = ctypes.POINTER(ctypes.c_int32)
            lib.ffd_group_of.restype = ctypes.c_int64
            lib.ffd_group_of.argtypes = [
                i64p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
                i32p,
            ]
            lib.lpt_group_of.restype = None
            lib.lpt_group_of.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i32p]
            lib.linear_partition_cuts.restype = None
            lib.linear_partition_cuts.argtypes = [
                i64p,
                ctypes.c_int64,
                ctypes.c_int64,
                i64p,
            ]
            _lib = lib
            logger.info(f"native datapack loaded from {path}")
        except Exception as e:  # noqa: BLE001 — fall back to pure Python
            _lib_failed = True
            logger.warning(f"native datapack unavailable ({e}); using Python")
    return _lib


def implementation() -> str:
    """Which datapack implementation this process uses: ``native`` (the
    g++-built shared object) or ``python`` (the fallback). Builds on first
    call, like any other use."""
    return "native" if datapack_lib() is not None else "python"
