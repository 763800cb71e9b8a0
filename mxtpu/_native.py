"""ctypes bridge to the native runtime (libmxtpu.so).

Parity: python/mxnet/base.py:99 ``_load_lib`` + the ``check_call`` /
``MXGetLastError`` error contract. The native library provides the
host-side runtime (storage pool, recordio, dependency engine, threaded
prefetch — see src/core/); everything device-side is JAX/XLA.

If the library is missing, we try a one-shot build via ``make -C src``
(toolchain is assumed present in dev images); failing that, the failure
is logged once at WARNING with make's stderr and every consumer takes
its pure-Python path — functional, without the native fast paths.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

from .base import _REPO_ROOT, NativeError

_LIB = None
# mxtpu: allow-raw-lock(library-loader bootstrap: taken once before
# any subsystem exists; leaf by construction)
_LIB_LOCK = threading.Lock()
_LIB_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "native", "libmxtpu.so")

# Producer callback for the threaded prefetcher: int fn(void* ctx, void** out)
PRODUCE_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p,
                              ctypes.POINTER(ctypes.c_void_p))
ASYNC_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


def _try_build():
    """One-shot ``make -C src``; runs at most once a process (get_lib
    latches the outcome), so a failure is reported exactly once."""
    src = os.path.join(_REPO_ROOT, "src")
    if not os.path.isfile(os.path.join(src, "Makefile")):
        return False
    try:
        subprocess.run(["make", "-C", src], check=True,
                       stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                       timeout=120)
    except (OSError, subprocess.SubprocessError) as exc:
        stderr = getattr(exc, "stderr", None) or b""
        logging.getLogger(__name__).warning(
            "native runtime build failed (%s); continuing on the "
            "pure-Python paths. make stderr:\n%s", exc,
            stderr.decode("utf-8", "replace")[-2000:])
        return False
    return os.path.isfile(_LIB_PATH)


def _declare(lib):
    lib.MXTPUGetLastError.restype = ctypes.c_char_p
    u64p = ctypes.POINTER(ctypes.c_uint64)
    vpp = ctypes.POINTER(ctypes.c_void_p)
    sigs = {
        "MXTPUStorageAlloc": [ctypes.c_uint64, vpp],
        "MXTPUStorageFree": [ctypes.c_void_p],
        "MXTPUStorageDirectFree": [ctypes.c_void_p],
        "MXTPUStorageReleaseAll": [],
        "MXTPUStorageStats": [u64p, u64p],
        "MXTPURecordWriterCreate": [ctypes.c_char_p, vpp],
        "MXTPURecordWriterWrite": [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint64],
        "MXTPURecordWriterTell": [ctypes.c_void_p, u64p],
        "MXTPURecordWriterFree": [ctypes.c_void_p],
        "MXTPURecordReaderCreate": [ctypes.c_char_p, vpp],
        "MXTPURecordReaderNext": [ctypes.c_void_p, vpp, u64p],
        "MXTPURecordReaderSeek": [ctypes.c_void_p, ctypes.c_uint64],
        "MXTPURecordReaderTell": [ctypes.c_void_p, u64p],
        "MXTPURecordReaderFree": [ctypes.c_void_p],
        "MXTPUEngineNewVar": [vpp],
        "MXTPUEngineDeleteVar": [ctypes.c_void_p],
        "MXTPUEnginePushAsync": [ASYNC_FN, ctypes.c_void_p, vpp,
                                 ctypes.c_int, vpp, ctypes.c_int,
                                 ctypes.c_int],
        "MXTPUEngineWaitForVar": [ctypes.c_void_p],
        "MXTPUEngineWaitForAll": [],
        "MXTPUEngineNumWorkers": [ctypes.POINTER(ctypes.c_int)],
        "MXTPUEngineOpsCompleted": [u64p],
        "MXTPUThreadedIterCreate": [PRODUCE_FN, ctypes.c_void_p,
                                    ctypes.c_int, vpp],
        "MXTPUThreadedIterNext": [ctypes.c_void_p, vpp],
        "MXTPUThreadedIterFree": [ctypes.c_void_p],
    }
    for name, argtypes in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = ctypes.c_int


def get_lib():
    """Load (building if needed) the native library; None if unavailable."""
    global _LIB
    if _LIB is not None:
        return _LIB if _LIB is not False else None
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB if _LIB is not False else None
        if os.environ.get("MXTPU_DISABLE_NATIVE", "0") == "1":
            _LIB = False
            return None
        if not os.path.isfile(_LIB_PATH) and not _try_build():
            _LIB = False
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
            _LIB = lib
        except OSError as exc:
            logging.getLogger(__name__).warning(
                "native runtime %s failed to load (%s); continuing on "
                "the pure-Python paths", _LIB_PATH, exc)
            _LIB = False
            return None
    return _LIB


def check_call(ret):
    """Raise NativeError with the native message on nonzero return."""
    if ret != 0:
        raise NativeError(get_lib().MXTPUGetLastError().decode("utf-8"))


def native_available():
    return get_lib() is not None
