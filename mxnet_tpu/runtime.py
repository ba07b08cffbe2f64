"""Native runtime bindings (ctypes over src/libmxtpu.so).

The reference implements its engine/storage/io core in C++
(src/engine/, src/storage/, src/io/ — SURVEY.md §2.1); here the same
components live in /root/repo/src and are loaded through a flat C ABI.
If the shared library is absent, it is built on first use when a
toolchain exists. Every consumer also has a pure-python path, so the
framework works without a compiler; that it is on that path is logged
once with the reason, and :func:`load_error` returns it.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess

_LIB = None
_TRIED = False
_ERROR = None


def _lib_path():
    return os.path.join(os.path.dirname(__file__), "libmxtpu.so")


def _src_dir():
    return os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def _build():
    """``make -C src``; returns None on success, else why it failed."""
    src = _src_dir()
    if not os.path.isdir(src):
        return "no source directory %s" % src
    try:
        r = subprocess.run(["make", "-C", src], capture_output=True,
                           text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return "make -C %s: %s" % (src, e)
    if r.returncode != 0:
        return "make -C %s exited %d: %s" % (
            src, r.returncode, (r.stderr or r.stdout).strip()[-2000:])
    if not os.path.exists(_lib_path()):
        return "make -C %s left no %s" % (src, _lib_path())
    return None


def load_error():
    """Why :func:`get_lib` returned None (None while it has not)."""
    return _ERROR


def _unavailable(reason):
    global _ERROR
    _ERROR = reason
    logging.getLogger("mxnet_tpu").warning(
        "native runtime unavailable, using the pure-python paths: %s",
        reason)
    return None


def get_lib():
    """Load (building if needed) the native library, or None — then the
    reason is logged once and kept for :func:`load_error`."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    path = _lib_path()
    if not os.path.exists(path):
        err = _build()
        if err is not None:
            return _unavailable(err)
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        return _unavailable("loading %s: %s" % (path, e))
    # engine
    lib.EngineCreate.restype = ctypes.c_void_p
    lib.EngineCreate.argtypes = [ctypes.c_int]
    lib.EngineDestroy.argtypes = [ctypes.c_void_p]
    lib.EngineNewVariable.restype = ctypes.c_int64
    lib.EngineNewVariable.argtypes = [ctypes.c_void_p]
    lib.EngineDeleteVariable.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.EnginePushAsync.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.EngineWaitForVar.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.EngineWaitForAll.argtypes = [ctypes.c_void_p]
    lib.EnginePendingCount.restype = ctypes.c_int
    lib.EnginePendingCount.argtypes = [ctypes.c_void_p]
    # storage
    lib.StorageCreate.restype = ctypes.c_void_p
    lib.StorageCreate.argtypes = [ctypes.c_uint64]
    lib.StorageDestroy.argtypes = [ctypes.c_void_p]
    lib.StorageAlloc.restype = ctypes.c_void_p
    lib.StorageAlloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.StorageFree.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.StorageDirectFree.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.StorageReleaseAll.argtypes = [ctypes.c_void_p]
    lib.StoragePooledBytes.restype = ctypes.c_uint64
    lib.StoragePooledBytes.argtypes = [ctypes.c_void_p]
    lib.StorageUsedBytes.restype = ctypes.c_uint64
    lib.StorageUsedBytes.argtypes = [ctypes.c_void_p]
    # recordio
    lib.RecordReaderCreate.restype = ctypes.c_void_p
    lib.RecordReaderCreate.argtypes = [ctypes.c_char_p]
    lib.RecordReaderDestroy.argtypes = [ctypes.c_void_p]
    lib.RecordReaderNum.restype = ctypes.c_int64
    lib.RecordReaderNum.argtypes = [ctypes.c_void_p]
    lib.RecordReaderGet.restype = ctypes.POINTER(ctypes.c_char)
    lib.RecordReaderGet.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                    ctypes.POINTER(ctypes.c_int64)]
    _LIB = lib
    return _LIB


def _require_lib():
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native runtime unavailable: %s" % load_error())
    return lib


_ENGINE_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


class NativeEngine:
    """Var-serialized async host scheduler (reference ThreadedEngine
    semantics: include/mxnet/engine.h PushAsync/WaitForVar/WaitForAll)."""

    def __init__(self, num_workers=None):
        if num_workers is None:
            from .config import flags
            num_workers = flags.cpu_worker_nthreads
        self._lib = lib = _require_lib()
        self._h = lib.EngineCreate(num_workers)
        # token -> cfn closure. A callback must NOT free its own libffi
        # closure (the worker thread still returns through it), so closures
        # are only retired after a native barrier (wait_all/close) proves
        # every outstanding callback has fully returned.
        self._keepalive = {}
        self._next = 0
        import threading
        self._mu = threading.Lock()

    def new_variable(self):
        return self._lib.EngineNewVariable(self._h)

    def delete_variable(self, var):
        self._lib.EngineDeleteVariable(self._h, var)

    def push(self, fn, const_vars=(), mutable_vars=()):
        """Schedule fn() after its dependencies; reads run concurrently."""
        with self._mu:
            self._next += 1
            token = self._next

        def trampoline(_arg, _fn=fn):
            _fn()
        cfn = _ENGINE_FN(trampoline)
        with self._mu:
            self._keepalive[token] = cfn
        n_c, n_m = len(const_vars), len(mutable_vars)
        c_arr = (ctypes.c_int64 * max(n_c, 1))(*const_vars)
        m_arr = (ctypes.c_int64 * max(n_m, 1))(*mutable_vars)
        self._lib.EnginePushAsync(
            self._h, ctypes.cast(cfn, ctypes.c_void_p), None,
            c_arr, n_c, m_arr, n_m)

    def wait_for_var(self, var):
        self._lib.EngineWaitForVar(self._h, var)

    def wait_all(self):
        self._lib.EngineWaitForAll(self._h)
        # barrier passed: every callback has returned; closures can go
        with self._mu:
            self._keepalive.clear()

    def pending(self):
        return self._lib.EnginePendingCount(self._h)

    def close(self):
        if self._h:
            self._lib.EngineDestroy(self._h)  # waits for all work
            self._h = None
            with self._mu:
                self._keepalive.clear()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeStoragePool:
    """Pooled host allocator (reference pooled_storage_manager.h)."""

    def __init__(self, reserve_limit=0):
        self._lib = lib = _require_lib()
        self._h = lib.StorageCreate(reserve_limit)

    def alloc(self, size):
        return self._lib.StorageAlloc(self._h, size)

    def free(self, ptr):
        self._lib.StorageFree(self._h, ptr)

    def direct_free(self, ptr):
        self._lib.StorageDirectFree(self._h, ptr)

    def release_all(self):
        self._lib.StorageReleaseAll(self._h)

    @property
    def pooled_bytes(self):
        return self._lib.StoragePooledBytes(self._h)

    @property
    def used_bytes(self):
        return self._lib.StorageUsedBytes(self._h)

    def close(self):
        if self._h:
            self._lib.StorageDestroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeRecordReader:
    """Zero-copy indexed RecordIO scanner (reference dmlc recordio)."""

    def __init__(self, path):
        self._lib = lib = _require_lib()
        self._h = lib.RecordReaderCreate(path.encode())
        if not self._h:
            raise IOError("failed to open/parse RecordIO file %s" % path)

    def __len__(self):
        return self._lib.RecordReaderNum(self._h)

    def __getitem__(self, i):
        n = ctypes.c_int64(0)
        p = self._lib.RecordReaderGet(self._h, i, ctypes.byref(n))
        if not p or n.value < 0:
            raise IndexError(i)
        return ctypes.string_at(p, n.value)

    def close(self):
        if self._h:
            self._lib.RecordReaderDestroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def available():
    return get_lib() is not None
