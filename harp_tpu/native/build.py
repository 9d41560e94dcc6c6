"""Compile-on-first-use for the native library (g++ → .so, ctypes ABI)."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import shutil
import subprocess
import tempfile

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "loader.cpp")
_LIB = None
_TRIED = False

log = logging.getLogger("harp_tpu.native")


def _so_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    # -march=native binaries are CPU-specific: key the cache on the CPU's
    # feature flags too, so a .so built on one machine never SIGILLs on
    # another sharing the package directory
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    h.update(line.encode())
                    break
    except OSError:
        import platform

        h.update(platform.processor().encode())
    return os.path.join(_DIR, f"_harp_native_{h.hexdigest()[:16]}.so")


def native_available() -> bool:
    return shutil.which("g++") is not None or os.path.exists(_so_path())


def load_native():
    """Return the ctypes library, building it if needed; None if
    impossible — the callers then run their numpy parsers (same results,
    slower).  Which of the two runs is logged once per process."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    so = _so_path()
    if not os.path.exists(so):
        if shutil.which("g++") is None:
            log.warning("no g++ and no built %s: the numpy parsers run",
                        os.path.basename(so))
            return None
        # build to a temp file then atomically rename (parallel-safe)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
        os.close(fd)
        cmd = ["g++", "-O3", "-march=native", "-std=c++17", "-shared",
               "-fPIC", "-pthread", _SRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True)
            os.replace(tmp, so)
        except subprocess.CalledProcessError as e:
            os.unlink(tmp)
            log.warning("g++ could not build loader.cpp, the numpy "
                        "parsers run: %s",
                        e.stderr.decode(errors="replace")[-500:])
            return None
    log.info("native parsers: %s", os.path.basename(so))
    lib = ctypes.CDLL(so)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.harp_count_rows.argtypes = [ctypes.c_char_p, ctypes.c_int, i64p, i64p]
    lib.harp_count_rows.restype = ctypes.c_int
    lib.harp_load_csv_f32.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64, ctypes.c_int64]
    lib.harp_load_csv_f32.restype = ctypes.c_int
    lib.harp_load_triples.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64]
    lib.harp_load_triples.restype = ctypes.c_int
    lib.harp_count_libsvm.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                      i64p, i64p, i64p]
    lib.harp_count_libsvm.restype = ctypes.c_int
    lib.harp_load_libsvm.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
        i64p, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_int64]
    lib.harp_load_libsvm.restype = ctypes.c_int
    lib.harp_csv_count_stream.argtypes = [ctypes.c_char_p, i64p, i64p]
    lib.harp_csv_count_stream.restype = ctypes.c_int
    lib.harp_csv_stream_open.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.harp_csv_stream_open.restype = ctypes.c_void_p
    lib.harp_csv_stream_cols.argtypes = [ctypes.c_void_p]
    lib.harp_csv_stream_cols.restype = ctypes.c_int64
    lib.harp_csv_stream_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    lib.harp_csv_stream_next.restype = ctypes.c_int64
    lib.harp_csv_stream_close.argtypes = [ctypes.c_void_p]
    lib.harp_csv_stream_close.restype = None
    _LIB = lib
    return _LIB
