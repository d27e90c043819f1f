"""ctypes bindings for host_runtime.cc (built lazily on the machine that
loads it). Raises at import when no toolchain is available — callers
catch and fall back to numpy."""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import tempfile

import numpy as np

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "host_runtime.cc")
_CXXFLAGS = ("-O3", "-march=native", "-std=c++17", "-shared", "-fPIC")


def _machine_id() -> bytes:
    """What a ``-march=native`` build is valid for: this boot of this
    machine (a checkout copied to another host — the chip machine —
    never loads a binary built for a different CPU)."""
    parts = [platform.machine()]
    for path in ("/proc/sys/kernel/random/boot_id", "/proc/cpuinfo"):
        try:
            with open(path) as f:
                text = f.read()
        except OSError:
            continue
        # cpuinfo: the first processor's block (model + feature flags).
        parts.append(text.split("\n\n", 1)[0])
    return "\0".join(parts).encode()


def _build() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(_machine_id())
    so_path = os.path.join(_DIR, f"_host_runtime_{h.hexdigest()[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
    os.close(fd)
    try:
        subprocess.run(
            ["g++", *_CXXFLAGS, _SRC, "-o", tmp],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so_path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    # Builds for other sources or machines are superseded, not reused.
    for stale in glob.glob(os.path.join(_DIR, "_host_runtime_*.so")):
        if stale != so_path:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return so_path


SO_PATH = _build()
_lib = ctypes.CDLL(SO_PATH)

_lib.fnv1a64_batch.argtypes = [
    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
]
_lib.fnv1a64_batch.restype = None
_lib.dict_encode_fixed.argtypes = [
    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
    ctypes.c_void_p, ctypes.c_int64,
    ctypes.c_void_p, ctypes.c_void_p,
]
_lib.dict_encode_fixed.restype = ctypes.c_int64


def _ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.c_void_p)


def fnv1a64_batch(strings) -> np.ndarray:
    """FNV-1a of each string's utf-8 bytes — bit-identical to the Python
    _fnv1a64 fallback."""
    encoded = [s.encode("utf-8") for s in strings]
    offsets = np.zeros(len(encoded) + 1, np.int64)
    np.cumsum([len(b) for b in encoded], out=offsets[1:])
    buf = np.frombuffer(b"".join(encoded), dtype=np.uint8)
    out = np.empty(len(encoded), np.uint64)
    if len(encoded):
        _lib.fnv1a64_batch(
            _ptr(np.ascontiguousarray(buf)) if buf.size else None,
            _ptr(offsets), len(encoded), _ptr(out),
        )
    return out


def encode_with_dict(values: np.ndarray, dict_values: list[str], u=None):
    """(codes int32, new_values list[str]): encode a string column against
    an existing dictionary; unseen values get fresh codes in
    first-occurrence order. Strings ride numpy's fixed-width U layout so
    the C++ side compares raw bytes. ``u`` lets callers reuse an already-
    converted fixed-width copy of ``values``."""
    arr = np.asarray(values, dtype=object)
    n = len(arr)
    if u is None:
        u = arr.astype("U")  # fixed-width UTF-32, C-speed conversion
    # Natural widths FIRST, then widen both to the common width — forcing
    # the dictionary into the batch's width would silently truncate longer
    # dictionary entries (and then alias their prefixes).
    dict_u = np.asarray(dict_values, dtype="U")
    width = max(u.dtype.itemsize, dict_u.dtype.itemsize, 4)
    if u.dtype.itemsize < width:
        u = u.astype(f"U{width // 4}")
    if dict_u.dtype.itemsize < width:
        dict_u = dict_u.astype(f"U{width // 4}")
    u = np.ascontiguousarray(u)
    dict_u = np.ascontiguousarray(dict_u)
    codes = np.empty(n, np.int32)
    new_rows = np.empty(n, np.int64)
    if n == 0:
        return codes, []
    n_new = _lib.dict_encode_fixed(
        _ptr(u), n, width,
        _ptr(dict_u) if len(dict_u) else None, len(dict_u),
        _ptr(codes), _ptr(new_rows),
    )
    new_values = [str(arr[i]) for i in new_rows[:n_new]]
    return codes, new_values
