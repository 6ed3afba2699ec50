"""ctypes bindings for the native runtime (libmxtpu).

The C++ side (src/cpp/) carries the reference's native-runtime roles on
TPU hosts (SURVEY §2.1): the dependency engine (threaded_engine.cc analog)
schedules host-side work — record IO, decode, prefetch — with MXNet's
read-var/write-var conflict semantics; the pooled buffer allocator plays
pooled_storage_manager.h for host staging buffers; the indexed RecordIO
reader + batch prefetcher are iter_image_recordio_2.cc/iter_prefetcher.h.
Device-side scheduling belongs to XLA's async dispatch and needs no C++.

The library is a git-ignored build product: it is built on demand with
g++ (make -C src/cpp) whenever it is missing or older than the committed
sources.  Every consumer falls back to pure python when unavailable
(``native.available()`` gates the fast paths; ``native.build_error()``
says why).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

__all__ = ["available", "build_error", "lib", "Engine", "RecordReader",
           "Prefetcher", "pool_stats"]

_LOCK = threading.Lock()
_LIB = None
_TRIED = False
_ERROR = None  # why the library is unavailable, for whoever reports it

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libmxtpu.so")
_SRC = os.path.normpath(os.path.join(_HERE, "..", "..", "src", "cpp"))


def _stale():
    """True when the library is missing or older than any committed
    source: the ``.so`` is a git-ignored build product, so one left on
    disk by another checkout state must not be loaded as if it matched
    ``src/cpp``."""
    if not os.path.isfile(_SO):
        return True
    if not os.path.isdir(_SRC):
        return False  # installed without sources: nothing to compare
    built = os.path.getmtime(_SO)
    return any(os.path.getmtime(os.path.join(_SRC, f)) > built
               for f in os.listdir(_SRC) if f.endswith((".cc", ".h")))


def _build():
    """``make -C src/cpp``; returns the failure reason, None on success."""
    if not os.path.isdir(_SRC):
        return f"no sources at {_SRC}"
    try:
        subprocess.run(["make", "-C", _SRC], check=True,
                       capture_output=True, text=True, timeout=300)
    except FileNotFoundError as e:
        return f"make not found: {e}"
    except subprocess.TimeoutExpired:
        return "make timed out (300s)"
    except subprocess.CalledProcessError as e:
        return f"make failed (rc={e.returncode}): {e.stderr[-500:]}"
    return None if os.path.isfile(_SO) else f"make produced no {_SO}"


def _bind(so):
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    so.MXTEngineCreate.restype = ctypes.c_void_p
    so.MXTEngineCreate.argtypes = [ctypes.c_int]
    so.MXTEngineDestroy.argtypes = [ctypes.c_void_p]
    so.MXTEngineNewVar.restype = ctypes.c_int64
    so.MXTEngineNewVar.argtypes = [ctypes.c_void_p]
    so.MXTEnginePush.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                 ctypes.c_void_p, i64p, ctypes.c_int,
                                 i64p, ctypes.c_int]
    so.MXTEngineWaitForVar.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    so.MXTEngineWaitAll.argtypes = [ctypes.c_void_p]
    so.MXTEngineVarVersion.restype = ctypes.c_uint64
    so.MXTEngineVarVersion.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    so.MXTGetLastError.restype = ctypes.c_char_p
    so.MXTRecordReaderCreate.restype = ctypes.c_void_p
    so.MXTRecordReaderCreate.argtypes = [ctypes.c_char_p]
    so.MXTRecordReaderDestroy.argtypes = [ctypes.c_void_p]
    so.MXTRecordReaderCount.restype = ctypes.c_int64
    so.MXTRecordReaderCount.argtypes = [ctypes.c_void_p]
    so.MXTRecordReaderSize.restype = ctypes.c_int64
    so.MXTRecordReaderSize.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    so.MXTRecordReaderOffset.restype = ctypes.c_int64
    so.MXTRecordReaderOffset.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    so.MXTRecordReaderRead.restype = ctypes.c_int
    so.MXTRecordReaderRead.argtypes = [ctypes.c_void_p, ctypes.c_int64, u8p]
    so.MXTPrefetcherCreate.restype = ctypes.c_void_p
    so.MXTPrefetcherCreate.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                       ctypes.c_int]
    so.MXTPrefetcherDestroy.argtypes = [ctypes.c_void_p]
    so.MXTPrefetcherSchedule.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int]
    so.MXTPrefetcherNext.restype = ctypes.c_int
    so.MXTPrefetcherNext.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(u8p),
                                     ctypes.POINTER(i64p),
                                     i64p, i64p]
    so.MXTBatchFree.argtypes = [u8p, i64p, ctypes.c_int64, ctypes.c_int64]
    so.MXTPoolStats.argtypes = [i64p, i64p]
    return so


def lib():
    """Load the native library, (re)building it from ``src/cpp`` when it
    is missing or older than the sources; None if unavailable (see
    :func:`build_error`)."""
    global _LIB, _TRIED, _ERROR
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("MXNET_TPU_NO_NATIVE"):
            _ERROR = "disabled by MXNET_TPU_NO_NATIVE"
            return None
        if _stale():
            _ERROR = _build()
            if _ERROR is not None:
                return None
        try:
            _LIB = _bind(ctypes.CDLL(_SO))
        except OSError as e:
            _ERROR = f"dlopen failed: {e}"
        return _LIB


def available():
    return lib() is not None


def build_error():
    """Why :func:`available` is False (None when the library loaded)."""
    lib()
    return _ERROR


def _i64arr(values):
    arr = (ctypes.c_int64 * len(values))(*values)
    return arr


_PUSH_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


class Engine:
    """Dependency engine handle (reference Engine::PushAsync semantics,
    include/mxnet/engine.h:?).  Python callbacks re-acquire the GIL, so use
    this for IO-bound tasks or as the scheduler under native ops."""

    def __init__(self, nthreads=4):
        self._so = lib()
        if self._so is None:
            raise RuntimeError("native library unavailable")
        self._h = self._so.MXTEngineCreate(nthreads)
        self._cbs = []  # keep callbacks alive until shutdown

    def new_var(self):
        return self._so.MXTEngineNewVar(self._h)

    def push(self, fn, read_vars=(), write_vars=()):
        cb = _PUSH_CB(lambda _arg: fn())
        self._cbs.append(cb)
        self._so.MXTEnginePush(
            self._h, ctypes.cast(cb, ctypes.c_void_p), None,
            _i64arr(list(read_vars)), len(read_vars),
            _i64arr(list(write_vars)), len(write_vars))

    def wait_for_var(self, var):
        self._so.MXTEngineWaitForVar(self._h, var)

    def wait_all(self):
        self._so.MXTEngineWaitAll(self._h)
        self._cbs.clear()

    def var_version(self, var):
        return self._so.MXTEngineVarVersion(self._h, var)

    def __del__(self):
        if getattr(self, "_h", None):
            self._so.MXTEngineDestroy(self._h)
            self._h = None


class RecordReader:
    """Indexed native RecordIO reader (pread-based, thread-safe)."""

    def __init__(self, path):
        self._so = lib()
        if self._so is None:
            raise RuntimeError("native library unavailable")
        self._h = self._so.MXTRecordReaderCreate(path.encode())
        if not self._h:
            raise IOError(self._so.MXTGetLastError().decode())

    def __len__(self):
        return self._so.MXTRecordReaderCount(self._h)

    def offset(self, i):
        """Byte offset of record i's first part header (maps .idx file
        offsets onto scan-order indices)."""
        return self._so.MXTRecordReaderOffset(self._h, i)

    def read(self, i):
        size = self._so.MXTRecordReaderSize(self._h, i)
        if size < 0:
            raise IndexError(f"record index {i} out of range")
        buf = np.empty(size, dtype=np.uint8)
        rc = self._so.MXTRecordReaderRead(
            self._h, i, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        if rc != 0:
            raise IOError("record read failed")
        return buf.tobytes()

    def close(self):
        if getattr(self, "_h", None):
            self._so.MXTRecordReaderDestroy(self._h)
            self._h = None

    def __del__(self):
        self.close()


class Prefetcher:
    """Batch prefetcher: schedule index lists, consume in schedule order.

    Wraps reader + engine; each batch returns a list of record payloads.
    Slots bound execution concurrency; the CALLER paces scheduling to
    bound buffered-batch memory (keep scheduled - consumed ~ capacity).
    """

    def __init__(self, path, nthreads=4, capacity=4):
        self._so = lib()
        if self._so is None:
            raise RuntimeError("native library unavailable")
        self._reader = RecordReader(path)
        self._engine = Engine(nthreads)
        self._h = self._so.MXTPrefetcherCreate(
            self._reader._h, self._engine._h, capacity)

    def __len__(self):
        return len(self._reader)

    def schedule(self, indices):
        idx = _i64arr([int(i) for i in indices])
        self._so.MXTPrefetcherSchedule(self._h, idx, len(indices))

    def next(self):
        """-> list[bytes] for the next scheduled batch; None when drained."""
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        data = u8p()
        offsets = i64p()
        n = ctypes.c_int64()
        nbytes = ctypes.c_int64()
        rc = self._so.MXTPrefetcherNext(
            self._h, ctypes.byref(data), ctypes.byref(offsets),
            ctypes.byref(n), ctypes.byref(nbytes))
        if rc == -1:
            return None
        if rc != 0:
            raise IOError(self._so.MXTGetLastError().decode())
        try:
            flat = np.ctypeslib.as_array(data, shape=(nbytes.value,)) \
                if nbytes.value else np.empty(0, np.uint8)
            offs = np.ctypeslib.as_array(offsets, shape=(n.value + 1,))
            return [flat[offs[j]:offs[j + 1]].tobytes()
                    for j in range(n.value)]
        finally:
            self._so.MXTBatchFree(data, offsets, n, nbytes)

    def close(self):
        if getattr(self, "_h", None):
            self._engine.wait_all()
            self._so.MXTPrefetcherDestroy(self._h)
            self._h = None
            self._reader.close()

    def __del__(self):
        self.close()


def pool_stats():
    """(hits, misses) of the native pooled buffer allocator."""
    so = lib()
    if so is None:
        return (0, 0)
    h = ctypes.c_int64()
    m = ctypes.c_int64()
    so.MXTPoolStats(ctypes.byref(h), ctypes.byref(m))
    return (h.value, m.value)
