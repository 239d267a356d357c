"""Native (C++) components, bound via ctypes.

Build-on-first-use: the shared library is compiled with g++ into this
package directory and cached; `load_packed_reader()` returns the bound
ctypes library or raises with the compiler error.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "packed_reader.cpp")
# the artifact lives in a non-package subdir: a .so directly inside the
# package looks like a CPython extension module to pkgutil/import tooling
_BUILD_DIR = os.path.join(_HERE, "_build")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _lib_path() -> str:
    """The artifact for THIS source: named by the source's content hash,
    so a stale library is never loaded — modification times do not
    survive a copy of the tree, and `_build/` is not in git."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"packed_reader.{digest}.so")


def _build(lib_path: str) -> None:
    # Compile to a process-unique temp path and rename atomically: several
    # processes (e.g. grain workers) may race the first build, and a
    # half-written .so must never be dlopen-able.
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native build failed: {' '.join(cmd)}\n{proc.stderr}")
    os.replace(tmp, lib_path)


def load_packed_reader() -> ctypes.CDLL:
    """Compile (unless built from this exact source) and bind the
    packed-record reader library."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            _build(lib_path)
        lib = ctypes.CDLL(lib_path)
        lib.pr_open.restype = ctypes.c_void_p
        lib.pr_open.argtypes = [ctypes.c_char_p]
        lib.pr_num_records.restype = ctypes.c_uint64
        lib.pr_num_records.argtypes = [ctypes.c_void_p]
        lib.pr_record_length.restype = ctypes.c_uint64
        lib.pr_record_length.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.pr_record_ptr.restype = ctypes.c_void_p
        lib.pr_record_ptr.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.pr_read_record.restype = ctypes.c_uint64
        lib.pr_read_record.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                       ctypes.c_void_p, ctypes.c_uint64]
        lib.pr_version.restype = ctypes.c_uint32
        lib.pr_version.argtypes = [ctypes.c_void_p]
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.pr_batch_length.restype = ctypes.c_uint64
        lib.pr_batch_length.argtypes = [ctypes.c_void_p, u64p,
                                        ctypes.c_uint64]
        lib.pr_read_batch.restype = ctypes.c_uint64
        lib.pr_read_batch.argtypes = [ctypes.c_void_p, u64p, ctypes.c_uint64,
                                      ctypes.c_void_p, ctypes.c_uint64, u64p]
        lib.pr_prefetch.restype = None
        lib.pr_prefetch.argtypes = [ctypes.c_void_p, u64p, ctypes.c_uint64]
        lib.pr_verify_record.restype = ctypes.c_int32
        lib.pr_verify_record.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.pr_verify_all.restype = ctypes.c_uint64
        lib.pr_verify_all.argtypes = [ctypes.c_void_p]
        lib.pr_close.restype = None
        lib.pr_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib
