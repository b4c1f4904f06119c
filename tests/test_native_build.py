"""The native libraries must COMPILE whenever a toolchain is present.

Round 3 shipped a compile error in arena_store.cc that silently degraded the
whole object plane to the Python fallback store because every consumer treated
"build failed" as "toolchain unavailable" and skipped. This gate makes a
compile error a loud test FAILURE: a from-scratch `make` in a temp dir with
RT_NATIVE_WERROR=1 (the CI-strict mode from native/Makefile) must produce all
four shared libraries.

Reference analog: the Bazel build of src/ray/object_manager/plasma is a hard
CI gate in /root/reference (BUILD.bazel targets fail the build on any compile
error); this is our equivalent for the ctypes-loaded native plane.
"""
import os
import shutil
import subprocess

import pytest

from ray_tpu import native as rt_native

_NATIVE_DIR = os.path.dirname(os.path.abspath(rt_native.__file__))

_TARGETS = [
    "librt_native.so",
    "librt_sched.so",
    "librt_xfer.so",
    "librt_ring.so",
]


@pytest.mark.skipif(
    not rt_native.toolchain_available(), reason="no g++/make toolchain"
)
def test_native_libs_build_from_scratch_werror(tmp_path):
    build = tmp_path / "native"
    build.mkdir()
    shutil.copy(os.path.join(_NATIVE_DIR, "Makefile"), build / "Makefile")
    shutil.copytree(os.path.join(_NATIVE_DIR, "src"), build / "src")
    env = dict(os.environ, RT_NATIVE_WERROR="1")
    res = subprocess.run(
        ["make", "-C", str(build)],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert res.returncode == 0, (
        "native build FAILED (this is a compile error in the repo, not an "
        "environment problem):\n" + res.stderr[-4000:]
    )
    for t in _TARGETS:
        assert (build / t).exists(), f"{t} missing after successful make"


@pytest.mark.skipif(
    not rt_native.toolchain_available(), reason="no g++/make toolchain"
)
def test_checked_in_libs_not_stale():
    """The lazy in-tree rebuild must succeed too (exercises the loader path
    workers actually take), and the loader must report no compile errors."""
    lib = rt_native.load_library()
    assert rt_native.build_failure() is None, rt_native.build_failure()
    assert lib is not None


@pytest.mark.skipif(
    not rt_native.toolchain_available(), reason="no g++/make toolchain"
)
def test_staleness_is_a_content_hash_not_an_mtime(tmp_path, monkeypatch):
    """A tree that was copied or unpacked carries arbitrary mtimes (and, on
    the chip machine, ``.so`` files built from other sources): a library is
    rebuilt exactly when the recorded hash of its sources differs."""
    build = tmp_path / "native"
    shutil.copytree(_NATIVE_DIR, build, ignore=shutil.ignore_patterns(
        "*.so", "*.srchash", "__pycache__", ".build.lock"))
    monkeypatch.setattr(rt_native, "_DIR", str(build))
    lib = str(build / "librt_ring.so")
    srcs = [str(build / "src" / "ring.cc")]

    (build / "librt_ring.so").write_bytes(b"a stale binary, newer than src")
    assert rt_native._lib_needs_build(lib, srcs)  # newer mtime, no hash
    assert rt_native.build_lib("librt_ring.so", lib, srcs)
    assert not rt_native._lib_needs_build(lib, srcs)
    assert rt_native.build_and_load("librt_ring.so", lib, srcs) is not None

    os.utime(srcs[0])  # touched, same bytes: still fresh
    assert not rt_native._lib_needs_build(lib, srcs)
    with open(srcs[0], "a") as f:
        f.write("\n// edited\n")
    os.utime(srcs[0], (0, 0))  # edited, but OLDER than the .so: stale
    assert rt_native._lib_needs_build(lib, srcs)
