"""Build + load the t3fs native library (g++ -> .so).

The artifact's name carries a hash of the sources and the build flags, so
a .so left over from other sources (an older checkout, another machine's
copy of the tree) is never loaded: a mismatch builds afresh."""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sysconfig
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["chunk_engine.cpp", "usrbio.cpp", "aio_reader.cpp",
            "sock_io.cpp"]
_LIB_STEM = os.path.join(_DIR, "libt3fs_native")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_loader: threading.Thread | None = None

import platform

_CXXFLAGS = ["-std=c++20", "-O2", "-g", "-fPIC", "-shared", "-Wall",
             "-pthread"]
# shm_open/shm_unlink (usrbio.cpp) live in librt before glibc 2.34;
# harmless stub library on newer glibc, required on e.g. Debian 11
_LDLIBS = ["-lrt"]
if platform.machine() in ("x86_64", "AMD64"):
    _CXXFLAGS.append("-msse4.2")  # hw CRC32C; other arches use the sw path

# Sanitizer builds (reference runs its suites under TSan —
# tsan_suppressions.txt): T3FS_SANITIZE=thread|address switches the build
# and the artifact name.  The sanitized .so needs the matching runtime
# loaded FIRST in the process (python itself is uninstrumented), so test
# runs set LD_PRELOAD=$(g++ -print-file-name=lib{tsan,asan}.so) — see
# `make sanitize`.
_SANITIZE = os.environ.get("T3FS_SANITIZE", "")


def _flags_and_variant() -> tuple[list[str], str]:
    if _SANITIZE and _SANITIZE not in ("thread", "address"):
        # an unknown value must not silently build UNinstrumented code
        # while the test harness believes it is in sanitizer mode
        raise ValueError(
            f"T3FS_SANITIZE={_SANITIZE!r}: use 'thread' or 'address'")
    if _SANITIZE in ("thread", "address"):
        flags = [f if f != "-O2" else "-O1" for f in _CXXFLAGS]
        flags.append(f"-fsanitize={_SANITIZE}")
        flags.append("-fno-omit-frame-pointer")
        return flags, f".{_SANITIZE[0]}san"
    return _CXXFLAGS, ""


def _sources() -> list[str]:
    return [os.path.join(_DIR, s) for s in _SOURCES
            if os.path.exists(os.path.join(_DIR, s))]


def _artifact(flags: list[str], variant: str, srcs: list[str]) -> str:
    h = hashlib.sha256("\0".join(flags + _LDLIBS).encode())
    for src in srcs:
        h.update(b"\0" + os.path.basename(src).encode() + b"\0")
        with open(src, "rb") as f:
            h.update(f.read())
    return f"{_LIB_STEM}{variant}-{h.hexdigest()[:16]}.so"


def build(force: bool = False) -> str:
    flags, variant = _flags_and_variant()
    srcs = _sources()
    lib = _artifact(flags, variant, srcs)
    if not force and os.path.exists(lib):
        return lib
    tmp = lib + f".tmp.{os.getpid()}"
    cmd = ["g++", *flags, "-o", tmp, *srcs, *_LDLIBS]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(f"native build failed:\n{e.stderr}") from e
    os.replace(tmp, lib)
    # drop artifacts of other sources; a process that mapped one keeps it
    for old in glob.glob(f"{_LIB_STEM}{variant}-*.so"):
        if old != lib:
            try:
                os.unlink(old)
            except FileNotFoundError:
                pass
    return lib


def load_library() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            _lib = ctypes.CDLL(build())
        return _lib


def loaded_library() -> ctypes.CDLL | None:
    """The library once this process has loaded it; before that None,
    having started one load (which may have to build) on a thread of its
    own, so a caller on an event loop never waits for a build.  A load
    that fails leaves None for good."""
    global _loader
    if _lib is None and _loader is None:
        _loader = threading.Thread(target=load_library, name="native-load",
                                   daemon=True)
        _loader.start()
    return _lib


if __name__ == "__main__":
    print(build(force=True))
