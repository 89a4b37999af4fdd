"""ctypes loader for the native C++ runtime library.

Analog of the reference's libmxnet.so discovery + ctypes FFI
(ref: python/mxnet/libinfo.py find_lib_path, python/mxnet/base.py _load_lib):
locates ``libmxnet_tpu.so`` next to the package, runs ``make -C src`` on
first use so the binary follows the sources (the reference ships a
prebuilt binary; here the toolchain is part of the environment), and
exposes the C ABI with
the reference's error convention — nonzero return → raise with
``MXTGetLastError()``.

Set ``MXNET_TPU_NO_NATIVE=1`` to force the pure-Python fallbacks.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess

from ._debug import locktrace as _locktrace
from .base import getenv as _getenv

_LIB = None
_LIB_LOCK = _locktrace.named_lock("native.lib")
_TRIED = False


def _lib_path():
    return os.path.join(os.path.dirname(__file__), "libmxnet_tpu.so")


def _src_dir():
    return os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def _build():
    """Bring ``libmxnet_tpu.so`` up to date with ``src/``: ``make``
    decides, and is a no-op when the binary is current, so a stale
    binary left in a working tree is never used as it is. False when
    the build failed — which is said aloud, because from then on every
    native path runs its pure-Python fallback."""
    src = _src_dir()
    try:
        import fcntl
        # serialize concurrent first-use builds (forked dataloader workers,
        # pytest-xdist): without the lock a second process can CDLL a
        # half-linked .so while make is still writing it
        with open(os.path.join(src, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                subprocess.run(["make", "-C", src], check=True,
                               capture_output=True, timeout=120)
            finally:
                fcntl.flock(lock, fcntl.LOCK_UN)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logging.warning(
            "native library build failed (%s: %s); using the pure-Python "
            "fallbacks", type(e).__name__,
            (getattr(e, "stderr", None) or str(e))[-500:])
        return False


def _declare(lib):
    u64 = ctypes.c_uint64
    p = ctypes.c_void_p
    pp = ctypes.POINTER(ctypes.c_void_p)
    charpp = ctypes.POINTER(ctypes.c_char_p)
    intp = ctypes.POINTER(ctypes.c_int)
    u64p = ctypes.POINTER(u64)
    lib.MXTGetLastError.restype = ctypes.c_char_p
    for name, argtypes in [
        ("MXTRecordWriterCreate", [ctypes.c_char_p, pp]),
        ("MXTRecordWriterWrite", [p, ctypes.c_char_p, u64]),
        ("MXTRecordWriterTell", [p, u64p]),
        ("MXTRecordWriterFree", [p]),
        ("MXTRecordReaderCreate", [ctypes.c_char_p, pp]),
        ("MXTRecordReaderNext", [p, charpp, u64p, intp]),
        ("MXTRecordReaderSeek", [p, u64]),
        ("MXTRecordReaderTell", [p, u64p]),
        ("MXTRecordReaderFree", [p]),
        ("MXTThreadedReaderCreate",
         [ctypes.c_char_p, u64, ctypes.c_int, u64, pp]),
        ("MXTThreadedReaderNext", [p, charpp, u64p, intp]),
        ("MXTThreadedReaderReset", [p]),
        ("MXTThreadedReaderFree", [p]),
    ]:
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = ctypes.c_int
    # predict ABI (only present when built with python3-config available)
    u32 = ctypes.c_uint32
    u32p = ctypes.POINTER(u32)
    fp = ctypes.POINTER(ctypes.c_float)
    for name, argtypes in [
        ("MXTPredCreate",
         [ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
          ctypes.c_int, u32, ctypes.POINTER(ctypes.c_char_p), u32p, u32p,
          pp]),
        ("MXTPredSetInput", [p, ctypes.c_char_p, fp, u32]),
        ("MXTPredForward", [p]),
        ("MXTPredGetOutputShape", [p, u32, ctypes.POINTER(u32p), u32p]),
        ("MXTPredGetOutput", [p, u32, fp, u32]),
        ("MXTPredReshape", [u32, ctypes.POINTER(ctypes.c_char_p), u32p,
                            u32p, p, pp]),
        ("MXTPredFree", [p]),
    ]:
        fn = getattr(lib, name, None)
        if fn is None:
            continue
        fn.argtypes = argtypes
        fn.restype = ctypes.c_int
    return lib


def get_lib():
    """The loaded native library, or None if unavailable/disabled."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LIB_LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if _getenv("MXNET_TPU_NO_NATIVE", "0") == "1":
            return None
        path = _lib_path()
        # with the sources at hand the binary follows them; without
        # (an installed wheel) the shipped binary is what there is
        if os.path.isdir(_src_dir()) and not _build():
            return None
        if not os.path.exists(path):
            return None
        try:
            _LIB = _declare(ctypes.CDLL(path))
        except OSError as e:
            logging.warning("native library load failed (%s); using the "
                            "pure-Python fallbacks", e)
            _LIB = None
    return _LIB


def check_call(ret):
    """ref: python/mxnet/base.py check_call."""
    if ret != 0:
        from .base import MXNetError
        raise MXNetError(get_lib().MXTGetLastError().decode("utf-8"))


def native_available():
    return get_lib() is not None


available = native_available  # runtime.Features probe name
