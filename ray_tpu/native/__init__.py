"""Native (C++) runtime components, loaded via ctypes.

The shared library is built from ``src/`` on first import (g++ is part of the
toolchain; there is no server process to deploy — the arena lives in shm and
every process coordinates through its header). A library is stale when the
content hash of its sources (and the Makefile) differs from the one recorded
beside it at build time, so a tree that was copied, checked out or unpacked
builds from the sources it holds whatever the files' mtimes say. If the
toolchain is missing or the build fails, importers fall back to the portable
Python implementations; ``load_report()`` says which happened.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import shutil
import subprocess
import threading

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "librt_native.so")
_SRC = os.path.join(_DIR, "src", "arena_store.cc")

_lock = threading.Lock()
_lib = None
_tried = False

# make_target -> compiler stderr for builds that FAILED with a working
# toolchain. A compile error is a bug in this repo, not an environment
# limitation — tests must fail (not skip) and bench must label fallback runs.
_build_errors: dict = {}


def toolchain_available() -> bool:
    return shutil.which("g++") is not None and shutil.which("make") is not None


def build_failure(target: str = None):
    """Compiler output for native targets that failed to COMPILE with the
    toolchain present, or None. Distinct from toolchain_available() so callers
    can tell "can't build here" from "the code is broken". Pass a make target
    (e.g. "librt_native.so") to scope the check to one library."""
    if target is not None:
        return _build_errors.get(target)
    if not _build_errors:
        return None
    return "\n".join(
        "%s:\n%s" % (t, err) for t, err in _build_errors.items()
    )


def _source_hash(srcs) -> str:
    h = hashlib.sha256()
    for path in sorted([*srcs, os.path.join(_DIR, "Makefile")]):
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _lib_needs_build(lib_path: str, srcs) -> bool:
    try:
        with open(lib_path + ".srchash") as f:
            recorded = f.read().strip()
    except OSError:
        return True
    return not os.path.exists(lib_path) or recorded != _source_hash(srcs)


def build_lib(make_target: str, lib_path: str, srcs) -> bool:
    """Build one native library under an exclusive file lock: N workers can
    start concurrently and must not relink the .so while another process
    dlopens it (the link itself is also atomic — temp output + rename, see
    Makefile). Shared by every native component's loader."""
    import fcntl

    try:
        with open(os.path.join(_DIR, ".build.lock"), "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            if not _lib_needs_build(lib_path, srcs):
                return True  # another process built while we waited
            # -B: make's own staleness rule is the mtime one this replaces
            res = subprocess.run(
                ["make", "-B", "-C", _DIR, make_target],
                capture_output=True,
                text=True,
                timeout=120,
            )
            if res.returncode == 0:
                with open(lib_path + ".srchash", "w") as f:
                    f.write(_source_hash(srcs))
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build (%s) unavailable: %s", make_target, e)
        return False
    if res.returncode != 0:
        if toolchain_available():
            _build_errors[make_target] = res.stderr[-2000:]
            logger.error(
                "native build (%s) FAILED with the toolchain present — this "
                "is a compile error in the repo, not a missing toolchain:\n%s",
                make_target,
                res.stderr[-2000:],
            )
        else:
            logger.warning(
                "native build (%s) failed:\n%s", make_target, res.stderr[-2000:]
            )
        return False
    return True


def build_and_load(make_target: str, lib_path: str, srcs):
    """Build (if stale) and dlopen one native library; None on failure.
    Callers cache the handle and set up their own argtypes."""
    if _lib_needs_build(lib_path, srcs):
        if not build_lib(make_target, lib_path, srcs):
            return None
    try:
        return ctypes.CDLL(lib_path)
    except OSError as e:
        logger.warning("native load of %s failed: %s", lib_path, e)
        return None


def _needs_build() -> bool:
    return _lib_needs_build(_LIB_PATH, [_SRC])


def _build() -> bool:
    return build_lib("librt_native.so", _LIB_PATH, [_SRC])


def load_library():
    """Return the ctypes lib, building it if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _needs_build():
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            logger.warning("native library load failed: %s", e)
            return None
        try:
            _bind_symbols(lib)
        except AttributeError as e:
            # A stale/mismatched .so (symbol missing) must degrade to the
            # fallback store, not crash worker startup.
            logger.error("native library symbol mismatch: %s", e)
            return None
        _lib = lib
        return _lib


def load_report() -> dict:
    """``{library: loaded}`` for the four native planes, building whatever
    is stale first — how a caller learns that a plane degraded to its
    Python fallback."""
    from ray_tpu.native import ring, sched, xfer

    return {
        "librt_native.so": load_library() is not None,
        "librt_sched.so": sched._load_library() is not None,
        "librt_xfer.so": xfer._load_library() is not None,
        "librt_ring.so": ring._load_library() is not None,
    }


def _bind_symbols(lib) -> None:
    lib.rt_arena_create.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32,
    ]
    lib.rt_arena_create.restype = ctypes.c_int
    lib.rt_arena_attach.argtypes = [ctypes.c_char_p]
    lib.rt_arena_attach.restype = ctypes.c_int
    lib.rt_arena_unlink.argtypes = [ctypes.c_char_p]
    lib.rt_arena_unlink.restype = ctypes.c_int
    lib.rt_arena_detach.argtypes = [ctypes.c_int]
    lib.rt_arena_detach.restype = ctypes.c_int
    lib.rt_arena_base.argtypes = [ctypes.c_int]
    lib.rt_arena_base.restype = ctypes.c_void_p
    lib.rt_arena_capacity.argtypes = [ctypes.c_int]
    lib.rt_arena_capacity.restype = ctypes.c_uint64
    lib.rt_obj_create.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_uint64,
    ]
    lib.rt_obj_create.restype = ctypes.c_int64
    lib.rt_obj_seal.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rt_obj_seal.restype = ctypes.c_int
    lib.rt_obj_get.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.rt_obj_get.restype = ctypes.c_int64
    lib.rt_obj_release.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rt_obj_release.restype = ctypes.c_int
    lib.rt_obj_delete.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rt_obj_delete.restype = ctypes.c_int
    lib.rt_obj_contains.argtypes = [ctypes.c_int, ctypes.c_char_p]
    lib.rt_obj_contains.restype = ctypes.c_int
    lib.rt_arena_stats.argtypes = [
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.rt_arena_stats.restype = None
    lib.rt_test_hold_lock.argtypes = [ctypes.c_int]
    lib.rt_test_hold_lock.restype = ctypes.c_int
    lib.rt_arena_num_tombs.argtypes = [ctypes.c_int]
    lib.rt_arena_num_tombs.restype = ctypes.c_uint64
    lib.rt_arena_scrub.argtypes = [ctypes.c_int]
    lib.rt_arena_scrub.restype = ctypes.c_int
    lib.rt_memcpy_parallel.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
    ]
    lib.rt_memcpy_parallel.restype = None
    lib.rt_arena_copy.argtypes = [
        ctypes.c_int, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
    ]
    lib.rt_arena_copy.restype = ctypes.c_int
