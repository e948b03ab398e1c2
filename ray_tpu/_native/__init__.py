"""Native (C++) runtime components, loaded via ctypes.

The compute path of ray_tpu is JAX/XLA; the runtime around it is native
where the reference's is (SURVEY.md §2.1): this package holds the C++
shared-memory arena object store (plasma equivalent —
/root/reference/src/ray/object_manager/plasma/) built as `librtpu_shm.so`.

Build model: `ensure_built()` compiles the .so with g++ on first use, cached
under _native/build/ (git-ignored) in a file named by the hash of
`shm_store.cc`'s content — a build directory that rode along from another
tree, or outlived an edit, is never loaded for this source; callers fall
back to the pure-python store when no toolchain is available.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "build")
_SRC = os.path.join(_HERE, "shm_store.cc")


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"librtpu_shm-{digest}.so")


_lock = threading.Lock()
_lib = None
_build_error = None


def ensure_built():  # graftlint: disable=lock-discipline — the build lock's purpose IS to serialize the one-time g++ build
    """Compile the native library if needed; returns the .so path or None."""
    global _build_error
    with _lock:
        so_path = _so_path()
        if os.path.exists(so_path):
            return so_path
        if _build_error is not None:
            return None
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # per-process temp name: concurrent first users (agent + workers)
        # each build, and the atomic rename makes any one of them win
        tmp = f"{so_path}.{os.getpid()}.tmp"
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
               "-o", tmp, _SRC, "-lrt", "-pthread"]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so_path)
            return so_path
        except (subprocess.CalledProcessError, FileNotFoundError,
                subprocess.TimeoutExpired) as e:
            _build_error = getattr(e, "stderr", b"") or str(e)
            return None


def build_error():
    return _build_error


def load_library():
    """ctypes-load the native store library (None if unavailable)."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
    path = ensure_built()
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    lib.rtpu_store_create.restype = ctypes.c_void_p
    lib.rtpu_store_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.rtpu_store_destroy.argtypes = [ctypes.c_void_p]
    lib.rtpu_store_put.restype = ctypes.c_int
    lib.rtpu_store_put.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_char_p, ctypes.c_uint64]
    lib.rtpu_store_seal.restype = ctypes.c_int
    lib.rtpu_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rtpu_store_get.restype = ctypes.c_int
    lib.rtpu_store_get.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_int)]
    lib.rtpu_store_pin.restype = ctypes.c_int
    lib.rtpu_store_pin.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int]
    lib.rtpu_store_delete.restype = ctypes.c_int
    lib.rtpu_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rtpu_store_stats.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64)]
    lib.rtpu_store_base.restype = ctypes.c_void_p
    lib.rtpu_store_base.argtypes = [ctypes.c_void_p]
    lib.rtpu_store_leak_mapping.restype = None
    lib.rtpu_store_leak_mapping.argtypes = [ctypes.c_void_p]
    with _lock:
        _lib = lib
    return lib
