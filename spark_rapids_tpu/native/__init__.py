"""Native runtime library: build-on-first-use C++ arena via ctypes.

The reference consumes RMM/pinned pools through JNI (SURVEY.md §2.9);
here the host arena + disk spill I/O are C++ (native/arena.cpp) loaded
with ctypes — no pybind11 in this image.  The compiled .so is cached
next to the source and rebuilt when the source changes.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_DIR, "arena.cpp"), os.path.join(_DIR, "lz4.cpp"),
         os.path.join(_DIR, "rows.cpp")]


def _so_path() -> str:
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_DIR, f"_native_{h.hexdigest()[:16]}.so")


def _build(so: str) -> None:
    # unique tmp name + atomic replace: concurrent builders each link
    # their own file and the rename is last-writer-wins, never garbled
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
    os.close(fd)
    try:
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", *_SRCS,
               "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_DIR, "_arena_*.so")) + \
            glob.glob(os.path.join(_DIR, "_native_*.so")):
        if stale != so:
            try:
                os.unlink(stale)
            except OSError:
                pass


_lib = None
_load_lock = threading.Lock()


def load() -> ctypes.CDLL:
    """Load (building if needed) the native arena library."""
    global _lib
    with _load_lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        _lib = _bind(ctypes.CDLL(so))
        return _lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.arena_create.restype = ctypes.c_void_p
    lib.arena_create.argtypes = [ctypes.c_size_t]
    lib.arena_destroy.argtypes = [ctypes.c_void_p]
    lib.arena_alloc.restype = ctypes.c_int64
    lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.arena_free.restype = ctypes.c_int
    lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.arena_base.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.arena_base.argtypes = [ctypes.c_void_p]
    lib.arena_capacity.restype = ctypes.c_size_t
    lib.arena_capacity.argtypes = [ctypes.c_void_p]
    lib.arena_used.restype = ctypes.c_size_t
    lib.arena_used.argtypes = [ctypes.c_void_p]
    lib.arena_largest_free.restype = ctypes.c_size_t
    lib.arena_largest_free.argtypes = [ctypes.c_void_p]
    lib.spill_write.restype = ctypes.c_int
    lib.spill_write.argtypes = [ctypes.c_char_p,
                                ctypes.POINTER(ctypes.c_uint8),
                                ctypes.c_size_t]
    lib.spill_read.restype = ctypes.c_int64
    lib.spill_read.argtypes = [ctypes.c_char_p,
                               ctypes.POINTER(ctypes.c_uint8),
                               ctypes.c_size_t]
    lib.lz4_compress_bound.restype = ctypes.c_size_t
    lib.lz4_compress_bound.argtypes = [ctypes.c_size_t]
    lib.lz4_compress.restype = ctypes.c_int64
    lib.lz4_compress.argtypes = [ctypes.POINTER(ctypes.c_uint8),
                                 ctypes.c_size_t,
                                 ctypes.POINTER(ctypes.c_uint8),
                                 ctypes.c_size_t]
    lib.lz4_decompress.restype = ctypes.c_int64
    lib.lz4_decompress.argtypes = [ctypes.POINTER(ctypes.c_uint8),
                                   ctypes.c_size_t,
                                   ctypes.POINTER(ctypes.c_uint8),
                                   ctypes.c_size_t]
    for fn, offset in ((lib.pad_rows32, ctypes.c_int32),
                       (lib.pad_rows64, ctypes.c_int64)):
        fn.restype = None
        fn.argtypes = [ctypes.c_void_p, ctypes.POINTER(offset),
                       ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                       ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
    return lib


def pad_rows(data, starts, lens, out) -> None:
    """Fill the C-contiguous uint8 matrix ``out`` (``[>= len(lens),
    width]``, uninitialized): row i gets its ``lens[i]`` bytes at
    ``data[starts[i]:]`` and zeros to the width, the rows past
    ``len(lens)`` zeros.  ``starts`` int32 or int64."""
    import numpy as np
    lib = load()
    fn, ctype = (lib.pad_rows32, ctypes.c_int32) \
        if starts.dtype == np.int32 else (lib.pad_rows64, ctypes.c_int64)
    starts = np.ascontiguousarray(starts)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    fn(data.ctypes.data, starts.ctypes.data_as(ctypes.POINTER(ctype)),
       lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
       len(lens), out.shape[1], out.ctypes.data, out.shape[0])


def lz4_compress(data) -> bytes:
    """LZ4 block-compress a bytes-like buffer (native codec)."""
    import numpy as np
    lib = load()
    src = np.frombuffer(data, dtype=np.uint8)
    bound = lib.lz4_compress_bound(src.size)
    dst = np.empty(bound, dtype=np.uint8)
    n = lib.lz4_compress(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), src.size,
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), bound)
    if n < 0:
        raise ValueError("lz4 compression overflow")
    return dst[:n].tobytes()


def lz4_decompress(data, out_size: int) -> bytes:
    """Decompress an LZ4 block into exactly ``out_size`` bytes."""
    import numpy as np
    lib = load()
    src = np.frombuffer(data, dtype=np.uint8)
    dst = np.empty(out_size, dtype=np.uint8)
    n = lib.lz4_decompress(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), src.size,
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), out_size)
    if n != out_size:
        raise ValueError(f"lz4 decompression failed ({n} != {out_size})")
    return dst.tobytes()


class HostArena:
    """Python handle over the C++ arena: numpy views into arena slices."""

    def __init__(self, capacity_bytes: int):
        import numpy as np
        self._lib = load()
        self._h = self._lib.arena_create(capacity_bytes)
        if not self._h:
            raise MemoryError(f"arena_create({capacity_bytes}) failed")
        base = self._lib.arena_base(self._h)
        cap = self._lib.arena_capacity(self._h)
        self._view = np.ctypeslib.as_array(base, shape=(cap,))
        self.capacity = cap

    def alloc(self, nbytes: int) -> int | None:
        off = self._lib.arena_alloc(self._h, max(nbytes, 1))
        return None if off < 0 else int(off)

    def free(self, offset: int) -> None:
        rc = self._lib.arena_free(self._h, offset)
        if rc != 0:
            raise ValueError(f"double/invalid free at offset {offset}")

    def view(self, offset: int, nbytes: int):
        """uint8 numpy view of an allocated slice (no copy)."""
        if self._view is None:
            raise ValueError("arena is closed")
        return self._view[offset:offset + nbytes]

    @property
    def used(self) -> int:
        return int(self._lib.arena_used(self._h))

    @property
    def largest_free(self) -> int:
        return int(self._lib.arena_largest_free(self._h))

    def _slice_ptr(self, offset: int):
        import ctypes as ct
        if self._view is None:
            raise ValueError("arena is closed")
        return ct.cast(ct.addressof(self._view.ctypes.data_as(
            ct.POINTER(ct.c_uint8)).contents) + offset,
            ct.POINTER(ct.c_uint8))

    def write_to_disk(self, offset: int, nbytes: int, path: str) -> None:
        rc = self._lib.spill_write(path.encode(), self._slice_ptr(offset),
                                   nbytes)
        if rc != 0:
            raise OSError(f"spill_write({path}) failed")

    def read_from_disk(self, offset: int, nbytes: int, path: str) -> None:
        got = self._lib.spill_read(path.encode(), self._slice_ptr(offset),
                                   nbytes)
        if got != nbytes:
            raise OSError(f"spill_read({path}): {got} != {nbytes}")

    def close(self) -> None:
        if self._h:
            # drop the view FIRST: any later access raises instead of
            # dereferencing unmapped pages (SIGSEGV)
            self._view = None
            self._lib.arena_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        # enginelint: disable=RL001 (interpreter-shutdown __del__: raising here aborts finalization)
        except Exception:
            pass
