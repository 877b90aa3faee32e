"""Native host kernels: build-on-first-use C++ with ctypes bindings.

Role-equivalent to the reference's native host layer (SURVEY.md §2.9 item 6 —
LightGBM's C++ dataset construction). The shared library is compiled from
kernels.cpp with the system toolchain on first use and cached next to the
package under a name keyed on the CONTENT of kernels.cpp — a copied checkout
does not keep mtimes honest, and a library built from another source text
must never be loaded. Every entry point has a pure-Python fallback so the
framework works without a compiler (`available()` reports which path is
active).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading

import numpy as np

_HERE = os.path.dirname(__file__)
# the library lives in a NON-package subdir: pkgutil walkers (e.g. the fuzz
# meta-test) import every module in package dirs, and a raw shared object is
# not a CPython extension module
_BUILD_DIR = os.path.join(_HERE, "build")
_SRC = os.path.join(_HERE, "kernels.cpp")
_lock = threading.Lock()
_lib = None
_build_failed = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"_native-{digest}.so")


def _build(so_path: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # compile to a per-process name, then rename: spawned ingest workers
    # may race this build, and a half-written library must never load
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if res.returncode != 0:
            return False
        os.replace(tmp, so_path)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load():
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        so_path = _so_path()
        if not os.path.exists(so_path) and not _build(so_path):
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(so_path)
        except OSError:
            _build_failed = True
            return None
        lib.murmur3_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_uint32, ctypes.c_int64, ctypes.c_void_p]
        lib.apply_bins.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p]
        lib.parse_csv_floats.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
        lib.parse_csv_floats.restype = ctypes.c_int64
        _lib = lib
        return _lib


def available() -> bool:
    """True when the compiled kernels are loadable (builds on first call)."""
    return _load() is not None


def hash_strings_native(values, seed: int = 0, num_bits: int = 0):
    """Batch murmur3 of a string sequence; returns int64 hashes masked to
    2^num_bits (0 = unmasked). None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    encoded = [str(v).encode("utf-8") for v in values]
    n = len(encoded)
    offsets = np.zeros(n + 1, np.int64)
    for i, b in enumerate(encoded):
        offsets[i + 1] = offsets[i] + len(b)
    blob = b"".join(encoded)
    buf = np.frombuffer(blob, np.uint8) if blob else np.zeros(1, np.uint8)
    out = np.empty(n, np.int64)
    mask = (1 << num_bits) - 1 if num_bits else 0
    lib.murmur3_batch(buf.ctypes.data, offsets.ctypes.data, n,
                      ctypes.c_uint32(seed), mask, out.ctypes.data)
    return out


def apply_bins_native(x: np.ndarray, upper_bounds: np.ndarray,
                      n_bins: int):
    """Host bin assignment over (n, F) f32 rows; None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, np.float32)
    ub = np.ascontiguousarray(upper_bounds, np.float32)
    n, f = x.shape
    out = np.empty((n, f), np.uint8)
    lib.apply_bins(x.ctypes.data, n, f, ub.ctypes.data, ub.shape[1],
                   n_bins, out.ctypes.data)
    return out


def parse_csv_native(text: bytes, cols: int, skip_rows: int = 0,
                     max_rows: int = None, return_clean: bool = False):
    """Parse comma-separated float rows; empty/unparseable fields become NaN.
    With return_clean, also returns a (cols,) bool array that is False for
    columns containing non-numeric text (incl. prefix-numeric strings like
    dates). None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(text, np.uint8) if text else np.zeros(1, np.uint8)
    cap = max_rows if max_rows is not None else text.count(b"\n") + 1
    out = np.empty((cap, cols), np.float32)
    clean = np.ones(cols, np.int64)
    n = lib.parse_csv_floats(buf.ctypes.data, len(text), cols, skip_rows,
                             out.ctypes.data, cap, clean.ctypes.data)
    if return_clean:
        return out[:n].copy(), clean.astype(bool)
    return out[:n].copy()
