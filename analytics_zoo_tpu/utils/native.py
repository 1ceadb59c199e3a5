"""ctypes bindings for the native C++ runtime pieces (csrc/).

Reference parity: the reference ships native code as external `zoo-core` artifacts
loaded through JNI stubs (SURVEY.md §2.9).  Here the native library builds on demand
from csrc/ with g++ (kept in build/ under a name derived from the source's content) and
binds through ctypes — no JNI, no pybind11.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CSRC = os.path.join(_REPO_ROOT, "csrc")
_BUILD = os.path.join(_REPO_ROOT, "build")

_lib = None
_lib_lock = threading.Lock()


_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC"]


def _build_library() -> str:
    """Build (or reuse) the library for the source AS IT IS NOW.  `build/`
    is git-ignored and travels with a copied tree, so a binary found there
    is trusted only through its name: the hash of the source bytes, the
    compile command and the machine type.  An mtime cannot tell a fresh
    binary from one built elsewhere from other source."""
    os.makedirs(_BUILD, exist_ok=True)
    src = os.path.join(_CSRC, "sample_store.cpp")
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX + [platform.machine()]).encode())
    out = os.path.join(_BUILD, f"libsamplestore-{h.hexdigest()[:16]}.so")
    if os.path.exists(out):
        return out
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(_CXX + ["-o", tmp, src, "-lpthread"], check=True,
                       capture_output=True)
        os.replace(tmp, out)      # atomic: concurrent builders both win
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return out


def get_lib() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is None:
            path = _build_library()
            lib = ctypes.CDLL(path)
            lib.ss_create.restype = ctypes.c_void_p
            lib.ss_create.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                      ctypes.c_int64]
            lib.ss_write.restype = ctypes.c_int
            lib.ss_write.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_void_p, ctypes.c_int64]
            lib.ss_write_bulk.restype = ctypes.c_int
            lib.ss_write_bulk.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                          ctypes.c_void_p, ctypes.c_int64]
            lib.ss_gather.restype = ctypes.c_int
            lib.ss_gather.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_int64, ctypes.c_void_p,
                                      ctypes.c_int]
            lib.ss_size.restype = ctypes.c_int64
            lib.ss_size.argtypes = [ctypes.c_void_p]
            lib.ss_destroy.argtypes = [ctypes.c_void_p]
            _lib = lib
        return _lib


class NativeSampleStore:
    """Fixed-stride sample arena with parallel minibatch gather.

    `path=None` -> anonymous RAM arena (DRAM tier); a file path -> mmap'd arena
    (DISK_AND_DRAM/PMEM tier)."""

    def __init__(self, n_samples: int, sample_shape, dtype=np.float32,
                 path: Optional[str] = None, n_threads: int = 4):
        self.lib = get_lib()
        self.sample_shape = tuple(int(i) for i in sample_shape)
        self.dtype = np.dtype(dtype)
        self.sample_bytes = int(np.prod(self.sample_shape) * self.dtype.itemsize)
        self.n_samples = int(n_samples)
        self.n_threads = n_threads
        self._h = self.lib.ss_create(
            path.encode() if path else None, self.n_samples, self.sample_bytes)
        if not self._h:
            raise MemoryError("failed to create native sample store")

    def write_bulk(self, start: int, samples: np.ndarray):
        arr = np.ascontiguousarray(samples, self.dtype)
        assert arr.shape[1:] == self.sample_shape
        rc = self.lib.ss_write_bulk(self._h, start,
                                    arr.ctypes.data_as(ctypes.c_void_p),
                                    arr.shape[0])
        if rc != 0:
            raise IndexError("write_bulk out of range")

    def gather(self, indices: np.ndarray) -> np.ndarray:
        idx = np.ascontiguousarray(indices, np.int64)
        out = np.empty((idx.shape[0],) + self.sample_shape, self.dtype)
        rc = self.lib.ss_gather(self._h, idx.ctypes.data_as(ctypes.c_void_p),
                                idx.shape[0],
                                out.ctypes.data_as(ctypes.c_void_p),
                                self.n_threads)
        if rc != 0:
            raise IndexError("gather index out of range")
        return out

    def close(self):
        if self._h:
            self.lib.ss_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __len__(self):
        return self.n_samples
