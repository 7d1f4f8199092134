"""ctypes loader for the native IO library (`src/io_native.cc`).

The reference ships its data plane in C++ (`src/io/`); here the hot
kernels live in `libmxtpu_io.so`, built lazily with the in-image
toolchain on first use and cached beside the sources.  The build is
``-march=native``, so a library is only trusted on the host it was built
for: a sidecar stamp records what it was built FROM and FOR (source,
Makefile, machine, CPU flags) and any mismatch rebuilds.  Every caller
keeps its numpy implementation (the reference the native results are
tested against): `lib()` returns None when the build fails or
`MXNET_USE_NATIVE_IO=0`.
"""
from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import platform
import subprocess

from .analysis import locks as _alocks

_log = logging.getLogger(__name__)

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
_LIB_PATH = os.path.join(_SRC_DIR, "libmxtpu_io.so")
_STAMP_PATH = _LIB_PATH + ".stamp"

_lock = _alocks.make_lock("native")
_lib = None
_tried = False


def _configure(lib):
    i64 = ctypes.c_int64
    lib.mxtpu_recordio_index.restype = i64
    lib.mxtpu_recordio_index.argtypes = [
        ctypes.c_void_p, i64, ctypes.POINTER(i64), ctypes.POINTER(i64),
        ctypes.POINTER(ctypes.c_int32), i64]
    lib.mxtpu_augment_to_chw.restype = None
    lib.mxtpu_augment_to_chw.argtypes = [
        ctypes.c_void_p, i64, i64, i64, i64, i64, i64, i64, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float), ctypes.c_int]
    lib.mxtpu_augment_batch.restype = None
    lib.mxtpu_augment_batch.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(i64),
        ctypes.POINTER(i64), i64, ctypes.POINTER(i64), ctypes.POINTER(i64),
        i64, i64, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float), i64, ctypes.c_int]
    lib.mxtpu_crop_batch_u8.restype = None
    lib.mxtpu_crop_batch_u8.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(i64),
        ctypes.POINTER(i64), i64, ctypes.POINTER(i64),
        ctypes.POINTER(i64), i64, i64, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_uint8), i64, ctypes.c_int]
    return lib


def _build_stamp():
    """sha256 over what the library is built from and for: the source,
    the Makefile (flags), the machine and its CPU feature flags."""
    h = hashlib.sha256()
    for name in ("io_native.cc", "Makefile"):
        with open(os.path.join(_SRC_DIR, name), "rb") as f:
            h.update(f.read())
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    h.update(("%s|%s" % (platform.machine(), flags)).encode())
    return h.hexdigest()


def _ensure_built():
    """Build `libmxtpu_io.so` unless its stamp says it was built from
    these sources for this host.  The stamp file doubles as the
    cross-process build lock."""
    want = _build_stamp()
    with open(_STAMP_PATH, "a+") as stamp:
        fcntl.flock(stamp, fcntl.LOCK_EX)
        stamp.seek(0)
        if stamp.read().strip() == want and os.path.exists(_LIB_PATH):
            return
        subprocess.run(["make", "-C", _SRC_DIR, "-s", "-B",
                        "libmxtpu_io.so"], check=True,
                       capture_output=True, timeout=120)
        stamp.seek(0)
        stamp.truncate()
        stamp.write(want + "\n")


def lib():
    """The loaded native library, building it if needed; None if
    unavailable (callers fall back to numpy)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("MXNET_USE_NATIVE_IO", "1") == "0":
            return None
        try:
            _ensure_built()
            _lib = _configure(ctypes.CDLL(_LIB_PATH))
        except subprocess.CalledProcessError as e:
            _log.warning("native IO library build failed; using the numpy "
                         "implementations:\n%s",
                         e.stderr.decode(errors="replace")[-2000:])
        except (OSError, subprocess.TimeoutExpired, AttributeError) as e:
            _log.warning("native IO library unavailable (%s); using the "
                         "numpy implementations", e)
        return _lib
