"""Build + ctypes bindings for the native ingest library.

The shared object is compiled on first use with the system g++ (cached next
to the source, keyed by a hash of the source's contents) — no build system,
no install step.
Everything degrades gracefully: ``lib()`` returns None when no compiler is
available and callers fall back to the pure-Python parsers.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

import platform as _platform

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "ingest.cpp")


def _so_path() -> str:
    """The library built from THIS ingest.cpp on this OS + arch: keyed by
    the source's contents, so a binary copied along with a checkout (the
    .so files are gitignored) is used only if it was built from the
    committed source — never on mtime alone."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(
        _DIR, f"_ingest_{sys.platform}_{_platform.machine()}"
              f"_py{sys.version_info[0]}{sys.version_info[1]}_{digest}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failed = False

_c_double_p = ctypes.POINTER(ctypes.c_double)
_c_int64_p = ctypes.POINTER(ctypes.c_int64)
_c_uint64_p = ctypes.POINTER(ctypes.c_uint64)
_c_int32_p = ctypes.POINTER(ctypes.c_int32)
_c_long_p = ctypes.POINTER(ctypes.c_long)


def _build(so: str) -> bool:
    if os.path.exists(so):
        return True
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent builders never share it
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC]
    try:
        r = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return False
    if r.returncode != 0:
        sys.stderr.write(f"native ingest build failed:\n{r.stderr.decode()[-2000:]}\n")
        return False
    os.replace(tmp, so)
    return True


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.sf_parse_points_csv.restype = ctypes.c_long
    lib.sf_parse_points_csv.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_char,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        _c_double_p, _c_double_p, _c_int64_p,
        _c_uint64_p, _c_int64_p, _c_int32_p,
        _c_int64_p, _c_long_p, _c_long_p,
    ]
    lib.sf_parse_points_geojson.restype = ctypes.c_long
    lib.sf_parse_points_geojson.argtypes = [
        ctypes.c_char_p, ctypes.c_long,
        ctypes.c_char_p, ctypes.c_char_p,
        _c_double_p, _c_double_p, _c_int64_p,
        _c_uint64_p, _c_int64_p, _c_int32_p,
        _c_int64_p, _c_long_p,
    ]
    lib.sf_parse_wkt_geoms.restype = ctypes.c_long
    lib.sf_parse_wkt_geoms.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_char,
        _c_int64_p, _c_uint64_p, _c_int64_p, _c_int32_p,
        ctypes.POINTER(ctypes.c_int8),
        _c_int64_p, _c_int32_p, _c_double_p,
        _c_int64_p, _c_int32_p,
        _c_double_p, _c_double_p,
        _c_int64_p, _c_long_p,
    ]
    lib.sf_parse_geojson_geoms.restype = ctypes.c_long
    lib.sf_parse_geojson_geoms.argtypes = [
        ctypes.c_char_p, ctypes.c_long,
        ctypes.c_char_p, ctypes.c_char_p,
        _c_int64_p, _c_uint64_p, _c_int64_p, _c_int32_p,
        ctypes.POINTER(ctypes.c_int8),
        _c_int64_p, _c_int32_p, _c_double_p,
        _c_int64_p, _c_int32_p,
        _c_double_p, _c_double_p,
        _c_int64_p, _c_long_p,
    ]
    return lib


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it if needed; None if unavailable
    (or disabled with SPATIALFLINK_NATIVE=0)."""
    global _lib, _failed
    if os.environ.get("SPATIALFLINK_NATIVE", "1") in ("0", "off", "no"):
        return None
    if _lib is not None or _failed:
        return _lib
    with _lock:
        if _lib is not None or _failed:
            return _lib
        so = _so_path()
        if not _build(so):
            _failed = True
            return None
        try:
            _lib = _bind(ctypes.CDLL(so))
        except OSError:
            # stale/corrupt binary: drop it and rebuild once from source
            try:
                os.remove(so)
            except OSError:
                pass
            if not _build(so):
                _failed = True
                return None
            try:
                _lib = _bind(ctypes.CDLL(so))
            except OSError:
                _failed = True
                return None
    return _lib


def available() -> bool:
    return lib() is not None
