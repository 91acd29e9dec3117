"""Native runtime bindings: builds native/*.cc into a shared library on
first use (g++ only — no pybind11 in this image) and exposes it via
ctypes.  Components: recordio, data loader, master service."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_PKG_DIR, "src")
_SOURCES = ["recordio.cc", "data_loader.cc", "master_service.cc",
            "optimizer.cc", "pserver_service.cc", "coord_store.cc",
            "memory.cc"]


def _lib_path() -> str:
    """Build target, named by the digest of the sources it is built
    from — a stale library copied along with a tree (the build output
    is git-ignored, mtimes do not survive a copy) can never be loaded
    for sources it does not match.  Next to the sources when writable
    (checkout / editable install), else a per-user cache dir."""
    h = hashlib.sha256()
    for s in _SOURCES:
        with open(os.path.join(_SRC_DIR, s), "rb") as f:
            h.update(f.read())
    name = f"libpaddle_tpu_native.{h.hexdigest()[:12]}.so"
    if os.access(_SRC_DIR, os.W_OK):
        return os.path.join(_SRC_DIR, name)
    cache = os.path.join(
        os.environ.get("XDG_CACHE_HOME",
                       os.path.join(os.path.expanduser("~"), ".cache")),
        "paddle_tpu")
    os.makedirs(cache, exist_ok=True)
    return os.path.join(cache, name)


_LIB_PATH = _lib_path()

_lock = threading.Lock()
_lib = None


def _build():
    if os.path.exists(_LIB_PATH):
        return
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"  # xdist workers build at once
    cmd = ["g++", "-std=c++17", "-O2", "-shared", "-fPIC", "-pthread",
           "-o", tmp] + srcs
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, _LIB_PATH)


def lib() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            _build()
            l = ctypes.CDLL(_LIB_PATH)
            # recordio
            l.recordio_writer_open.restype = ctypes.c_void_p
            l.recordio_writer_open.argtypes = [ctypes.c_char_p]
            l.recordio_write.restype = ctypes.c_int
            l.recordio_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_uint32]
            l.recordio_writer_close.argtypes = [ctypes.c_void_p]
            l.recordio_reader_open.restype = ctypes.c_void_p
            l.recordio_reader_open.argtypes = [ctypes.c_char_p]
            l.recordio_read.restype = ctypes.c_long
            l.recordio_read.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_uint8),
                                        ctypes.c_uint32]
            l.recordio_reader_close.argtypes = [ctypes.c_void_p]
            # loader
            l.dl_open.restype = ctypes.c_void_p
            l.dl_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int]
            l.dl_next.restype = ctypes.c_long
            l.dl_next.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_uint8),
                                  ctypes.c_uint32]
            l.dl_close.argtypes = [ctypes.c_void_p]
            # master
            l.master_start.restype = ctypes.c_void_p
            l.master_start.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
            l.master_port.restype = ctypes.c_int
            l.master_port.argtypes = [ctypes.c_void_p]
            l.master_stop.argtypes = [ctypes.c_void_p]
            # optimizer C lib (reference paddle/optimizer/optimizer.h)
            l.opt_create.restype = ctypes.c_void_p
            l.opt_create.argtypes = [ctypes.c_char_p,
                                     ctypes.POINTER(ctypes.c_float),
                                     ctypes.c_uint64]
            l.opt_destroy.argtypes = [ctypes.c_void_p]
            l.opt_update.restype = ctypes.c_int
            l.opt_update.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_float),
                                     ctypes.c_uint64]
            l.opt_update_rows.restype = ctypes.c_int
            l.opt_update_rows.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_float),
                                          ctypes.POINTER(ctypes.c_int64),
                                          ctypes.c_uint64, ctypes.c_uint64]
            l.opt_weight_count.restype = ctypes.c_uint64
            l.opt_weight_count.argtypes = [ctypes.c_void_p]
            l.opt_get_weights.restype = ctypes.c_int
            l.opt_get_weights.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_float),
                                          ctypes.c_uint64]
            l.opt_step.restype = ctypes.c_int64
            l.opt_step.argtypes = [ctypes.c_void_p]
            l.opt_serialize_size.restype = ctypes.c_uint64
            l.opt_serialize_size.argtypes = [ctypes.c_void_p]
            l.opt_serialize.restype = ctypes.c_int64
            l.opt_serialize.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_uint8),
                                        ctypes.c_uint64]
            l.opt_deserialize.restype = ctypes.c_void_p
            l.opt_deserialize.argtypes = [ctypes.POINTER(ctypes.c_uint8),
                                          ctypes.c_uint64]
            # pserver service
            l.pserver_start.restype = ctypes.c_void_p
            l.pserver_start.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                        ctypes.c_int]
            l.pserver_port.restype = ctypes.c_int
            l.pserver_port.argtypes = [ctypes.c_void_p]
            l.pserver_stop.argtypes = [ctypes.c_void_p]
            # coordination store (etcd equivalent)
            l.coord_start.restype = ctypes.c_void_p
            l.coord_start.argtypes = [ctypes.c_int]
            l.coord_port.restype = ctypes.c_int
            l.coord_port.argtypes = [ctypes.c_void_p]
            l.coord_stop.argtypes = [ctypes.c_void_p]
            # host staging memory (buddy allocator)
            l.mem_pool_create.restype = ctypes.c_void_p
            l.mem_pool_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
            l.mem_pool_destroy.argtypes = [ctypes.c_void_p]
            l.mem_alloc.restype = ctypes.c_void_p
            l.mem_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            l.mem_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            l.mem_used.restype = ctypes.c_uint64
            l.mem_used.argtypes = [ctypes.c_void_p]
            l.mem_pool_bytes.restype = ctypes.c_uint64
            l.mem_pool_bytes.argtypes = [ctypes.c_void_p]
            _lib = l
    return _lib


class RecordIOWriter:
    def __init__(self, path: str):
        self._lib = lib()
        self._h = self._lib.recordio_writer_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def write(self, data: bytes):
        if self._lib.recordio_write(self._h, data, len(data)) != 0:
            raise IOError("recordio write failed")

    def close(self):
        if self._h:
            self._lib.recordio_writer_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class RecordIOReader:
    def __init__(self, path: str, max_record: int = 16 << 20):
        self._lib = lib()
        self._h = self._lib.recordio_reader_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")
        self._buf = (ctypes.c_uint8 * max_record)()
        self._cap = max_record

    def __iter__(self):
        return self

    def __next__(self) -> bytes:
        n = self._lib.recordio_read(self._h, self._buf, self._cap)
        if n == -1:
            self.close()
            raise StopIteration
        if n < 0:
            raise IOError(f"corrupt record (code {n})")
        return bytes(bytearray(self._buf[: n]))

    def close(self):
        if self._h:
            self._lib.recordio_reader_close(self._h)
            self._h = None


class DataLoader:
    """Prefetching reader over recordio shards (native threads)."""

    def __init__(self, paths, num_threads: int = 2, capacity: int = 256,
                 max_record: int = 16 << 20):
        self._lib = lib()
        csv = ",".join(paths).encode()
        self._h = self._lib.dl_open(csv, num_threads, capacity, max_record)
        self._buf = (ctypes.c_uint8 * max_record)()
        self._cap = max_record

    def __iter__(self):
        return self

    def __next__(self) -> bytes:
        n = self._lib.dl_next(self._h, self._buf, self._cap)
        if n == -1:
            raise StopIteration
        if n < 0:
            raise IOError("record larger than buffer")
        return bytes(bytearray(self._buf[: n]))

    def close(self):
        if self._h:
            self._lib.dl_close(self._h)
            self._h = None

    def reader(self):
        """v2-style reader factory."""

        def _r():
            for rec in self:
                yield rec

        return _r
